#!/usr/bin/env python3
"""Bring-up smoke run of PerFedS² on a TPU, through the normal entry points.

    python3 chip_smoke.py             # one chip: simulator + mamba2_370m step
    python3 chip_smoke.py --chips 4   # four chips: semi-sync cohorts across
                                      # chips, checked against one device

One chip runs two phases:

1. simulator — ``fl.simulation.run_simulation`` at the paper's Table-I
   setup (mnist_dnn, 20 UEs, A=5, S=5, non-iid l=4), then a mobile run of
   a 4-cell hierarchy at 1024 UEs.  It checks that the personalized loss
   is finite and falls, that every Π row sums to A, and that the engine's
   Eq.-8 round compiles to the Pallas kernel and agrees with the jnp path.
2. train — ``semi_sync.make_train_step(perfed_step=True)`` via
   ``launch.specs.build_case`` for mamba2_370m at published widths and
   full depth, a few steps on random weights and tokens from a seed.

Earlier lines are informational (compile and step seconds, peak device
memory); they are not benchmark numbers.  The last line is one JSON object
``{"ok": true, "device": {...}}``.  The script exits non-zero without
printing it when JAX finds no TPU or any phase fails.  It is one process
and never starts another.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding
from repro.config import (ExperimentConfig, FLConfig, MobilityConfig,
                          ShapeConfig)
from repro.configs import get_config
from repro.data import partition_noniid, synthetic_mnist
from repro.fl.engine import SimulationEngine, bucket_size
from repro.fl.simulation import run_simulation
from repro.kernels.stale_aggregate import _resolve_backend
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.specs import arch_rules, build_case
from repro.models import build_model
from repro.utils.tree import TreeFlattener, tree_norm

# mobile point: the benchmarks/event_loop.py base point (1024 UEs, 4 cells,
# 20 m/s random waypoint, cell→cloud hierarchy)
MOBILE_UES, MOBILE_CELLS, MOBILE_SPEED = 1024, 4, 20.0
# train phase: three 4×2048 batches (inner, outer, Hessian) of Eq. 7
TRAIN_SEQ, TRAIN_BATCH = 2048, 4


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or non-finite result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(device=None):
    """Peak bytes in use so far on ``device`` (default: device 0); None
    where the backend keeps no memory stats."""
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _finite(x) -> bool:
    return math.isfinite(float(x))


# ---------------------------------------------------------------------------
# phase 1: the event-driven simulator
# ---------------------------------------------------------------------------

def _table1_setup(data_n: int):
    cfg = ExperimentConfig(
        model=get_config("mnist_dnn"),
        fl=FLConfig(n_ues=20, participants_per_round=5, staleness_bound=5,
                    alpha=0.03, beta=0.07,
                    inner_batch=16, outer_batch=16, hessian_batch=16))
    model = build_model(cfg.model)
    clients = partition_noniid(synthetic_mnist(n=data_n), cfg.fl.n_ues,
                               n_labels=4)
    return cfg, model, clients


def _mobile_setup(n_ues: int):
    cfg = ExperimentConfig(
        model=get_config("mnist_dnn"),
        fl=FLConfig(n_ues=n_ues, participants_per_round=max(1, n_ues // 16),
                    staleness_bound=8, alpha=0.03, beta=0.07,
                    first_order=True,
                    inner_batch=4, outer_batch=4, hessian_batch=4),
        mobility=MobilityConfig(
            enabled=True, model="random_waypoint", speed_mps=MOBILE_SPEED,
            n_cells=MOBILE_CELLS, hierarchy=True, cloud_sync_every=4))
    model = build_model(cfg.model)
    clients = partition_noniid(
        synthetic_mnist(n=max(2500, 10 * n_ues)), n_ues, n_labels=4)
    return cfg, model, clients


def check_eq8_round(engine, model, clients, fl, *, seed: int = 0) -> dict:
    """Compile the engine's fused Eq.-8 round once and run it against the
    same round on the jnp aggregation path.

    The round is the all-lanes program ``round_update`` dispatches when
    every lane holds its own model version: A lanes padded to a power-of-2
    bucket with zero weight, vmapped Eq.-7 payloads, then Eq. 8.
    """
    on_tpu = jax.default_backend() == "tpu"
    backend = _resolve_backend(engine.agg_backend)
    check(backend == ("pallas" if on_tpu else "jnp"),
          f"Eq.-8 backend resolved to {backend!r} on "
          f"{jax.default_backend()!r}")

    key = jax.random.PRNGKey(seed)
    params = model.init(key)
    flat = TreeFlattener.for_tree(params)
    m = fl.participants_per_round
    bucket = bucket_size(m, engine.max_bucket)
    base = flat.flatten(params)
    # distinct stale versions, one per lane
    versions = tuple(
        base + 0.01 * jax.random.normal(jax.random.fold_in(key, i),
                                        base.shape)
        for i in range(bucket))
    lanes = [i % len(clients) for i in range(bucket)]
    trips = [clients[i].sample_triplet(fl.inner_batch, fl.outer_batch,
                                       fl.hessian_batch) for i in lanes]
    batches = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *trips)
    weights = np.zeros(bucket, np.float32)
    weights[:m] = 1.0
    args = (params, versions, batches, jnp.arange(bucket, dtype=jnp.int32),
            jnp.full((bucket,), fl.alpha, jnp.float32), jnp.asarray(weights),
            float(fl.beta), jax.random.fold_in(key, 99))

    t0 = time.perf_counter()
    compiled = engine._get_round_fn(flat).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in compiled.as_text()
    check(has_kernel == on_tpu,
          f"compiled Eq.-8 round: tpu_custom_call present={has_kernel} "
          f"on {jax.default_backend()!r}")

    _, got = compiled(*args)
    ref_engine = SimulationEngine(model, fl, engine.algorithm,
                                  agg_backend="jnp")
    _, want = ref_engine._get_round_fn(flat)(*args)
    got, want = np.asarray(got), np.asarray(want)
    check(bool(np.isfinite(got).all()), "Eq.-8 round output not finite")
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    step = float(np.max(np.abs(want - np.asarray(base))))
    check(err <= 1e-5 * max(1.0, scale),
          f"Eq.-8 {backend} round differs from jnp by {err} "
          f"(param scale {scale}, update {step})")
    return {"backend": backend, "tpu_custom_call": has_kernel,
            "compile_s": compile_s, "max_abs_err_vs_jnp": err,
            "update_max_abs": step, "n_params": int(base.shape[0]),
            "lanes": bucket}


def simulator_phase(*, rounds: int = 30, mobile_ues: int = MOBILE_UES,
                    mobile_rounds: int = 8, data_n: int = 4000) -> dict:
    out = {}
    cfg, model, clients = _table1_setup(data_n)
    engine = SimulationEngine(model, cfg.fl, "perfed")
    t0 = time.perf_counter()
    res = run_simulation(cfg, model, clients, algorithm="perfed",
                         mode="semi", max_rounds=rounds,
                         eval_every=max(1, rounds // 3), engine=engine)
    wall = time.perf_counter() - t0
    losses = np.asarray(res.losses, float)
    a = cfg.fl.participants_per_round
    check(len(losses) >= 2 and bool(np.isfinite(losses).all()),
          f"static run: personalized losses {losses.tolist()}")
    check(losses[-1] < losses[0],
          f"static run: personalized loss did not fall {losses.tolist()}")
    check(res.pi.shape[0] == rounds and bool((res.pi.sum(1) == a).all()),
          f"static run: Π row sums {sorted(set(res.pi.sum(1).tolist()))}, "
          f"{res.pi.shape[0]} rounds; want {rounds} rounds of A={a}")
    out["static"] = {"rounds": int(res.pi.shape[0]),
                     "loss_first": float(losses[0]),
                     "loss_last": float(losses[-1]),
                     "wall_s_cold": wall,
                     "payload_dispatches": res.payload_dispatches}
    log(f"simulator/static: {out['static']}")

    out["eq8_round"] = check_eq8_round(engine, model, clients, cfg.fl)
    log(f"simulator/eq8_round: {out['eq8_round']}")

    mcfg, mmodel, mclients = _mobile_setup(mobile_ues)
    t0 = time.perf_counter()
    mres = run_simulation(mcfg, mmodel, mclients, algorithm="perfed",
                          mode="semi", bandwidth_policy="equal",
                          max_rounds=mobile_rounds, eval_every=mobile_rounds)
    wall = time.perf_counter() - t0
    mlosses = np.asarray(mres.losses, float)
    check(mres.pi.shape[0] == mobile_rounds,
          f"mobile run: {mres.pi.shape[0]} of {mobile_rounds} rounds")
    check(len(mlosses) >= 1 and bool(np.isfinite(mlosses).all()),
          f"mobile run: personalized losses {mlosses.tolist()}")
    check(mres.aborted_rounds == 0,
          f"mobile run: {mres.aborted_rounds} aborted rounds")
    out["mobile"] = {"n_ues": mobile_ues, "n_cells": mres.n_cells,
                     "rounds": int(mres.pi.shape[0]),
                     "cloud_rounds": mres.cloud_rounds,
                     "handovers": mres.handovers,
                     "loss_last": float(mlosses[-1]), "wall_s_cold": wall}
    log(f"simulator/mobile: {out['mobile']}")
    log(f"simulator: peak_bytes_in_use={peak_bytes()}")
    return out


# ---------------------------------------------------------------------------
# phase 2: the SPMD PerFedS² train step
# ---------------------------------------------------------------------------

def _random_batches(case, vocab: int, key, shardings=None):
    """Token batches shaped like ``case.args[1]``, drawn on device."""
    leaves, treedef = jax.tree.flatten(case.args[1])

    def draw(k):
        keys = jax.random.split(k, len(leaves))
        return jax.tree.unflatten(treedef, [
            jax.random.randint(kk, a.shape, 0, vocab, a.dtype)
            for kk, a in zip(keys, leaves)])

    return jax.jit(draw, out_shardings=shardings)(key)


def _compile(case):
    """AOT-compile ``case.fn`` with its shardings, state donated."""
    t0 = time.perf_counter()
    compiled = jax.jit(case.fn, in_shardings=case.in_shardings,
                       out_shardings=case.out_shardings,
                       donate_argnums=0).lower(*case.args).compile()
    return compiled, time.perf_counter() - t0


def train_phase(model_cfg, *, seq_len: int = TRAIN_SEQ,
                batch: int = TRAIN_BATCH, steps: int = 3,
                seed: int = 0) -> dict:
    """A few PerFedS² steps of ``model_cfg`` on one device."""
    mesh = make_host_mesh()
    shape = ShapeConfig("smoke", seq_len=seq_len, global_batch=batch,
                        kind="train")
    key = jax.random.PRNGKey(seed)
    with sharding.use_mesh(mesh, arch_rules(model_cfg, mesh)):
        case = build_case(model_cfg, shape, mesh, perfed_step=True,
                          seed=seed)
        compiled, compile_s = _compile(case)
        mem = compiled.memory_analysis()
        state = jax.jit(case.init, out_shardings=case.in_shardings[0])(key)
        hist = []
        for i in range(steps):
            k = jax.random.fold_in(key, i + 1)
            batches = _random_batches(case, model_cfg.vocab_size,
                                      jax.random.fold_in(k, 0),
                                      case.in_shardings[1])
            jax.block_until_ready(batches)
            t0 = time.perf_counter()
            state, metrics = compiled(state, batches, k)
            loss = float(metrics["loss"])
            gnorm = float(metrics["grad_norm"])
            hist.append({"step": i, "loss": loss, "grad_norm": gnorm,
                         "step_s": time.perf_counter() - t0})
            check(_finite(loss) and _finite(gnorm),
                  f"{case.name} step {i}: loss={loss} grad_norm={gnorm}")
            log(f"train/{case.name}: {hist[-1]}")
    out = {"case": case.name, "compile_s": compile_s, "steps": hist,
           "peak_bytes_in_use": peak_bytes()}
    if mem is not None:
        out["argument_bytes"] = mem.argument_size_in_bytes
        out["temp_bytes"] = mem.temp_size_in_bytes
    log(f"train: compile_s={compile_s:.1f} "
        f"argument_bytes={out.get('argument_bytes')} "
        f"temp_bytes={out.get('temp_bytes')} "
        f"peak_bytes_in_use={out['peak_bytes_in_use']}")
    return out


# ---------------------------------------------------------------------------
# four chips: semi-sync cohorts across chips
# ---------------------------------------------------------------------------

# π_k per step (1 = the cohort's buffered gradient arrives this round)
_MASKS = ((1, 1, 1, 1), (1, 0, 1, 1), (0, 1, 1, 0), (1, 1, 0, 1))


def _semi_sync_case(model_cfg, mesh, cohorts: int, seq_len: int,
                    per_cohort: int, seed: int):
    shape = ShapeConfig("smoke", seq_len=seq_len,
                        global_batch=cohorts * per_cohort, kind="train")
    with sharding.use_mesh(mesh, arch_rules(model_cfg, mesh)):
        return build_case(model_cfg, shape, mesh, semi_sync_cohorts=cohorts,
                          seed=seed)


def _mask(i: int, cohorts: int):
    return jnp.asarray(_MASKS[i % len(_MASKS)][:cohorts], jnp.float32)


def semi_sync_compare(model_cfg, *, cohorts: int = 4, seq_len: int,
                      per_cohort: int, steps: int = 3, seed: int = 0,
                      rtol: float = 2e-2) -> dict:
    """The semi-sync step with one cohort per device, against the same
    step on one device with the same inputs, mask and key.

    The parameters' change over ``steps`` steps must agree within bf16
    tolerance (``rtol`` of its largest element).  Both sides run in float32
    with f32 matmuls: in bf16 the Eq.-8 update (β times a gradient clipped
    to norm 1) rounds away on most elements, and at random init the Eq.-7
    step turns a 1e-4 relative change in the parameters into a 1% change in
    the update, so a TPU's default bf16 matmul passes would hide the
    sharding behind rounding.
    """
    model_cfg = dataclasses.replace(model_cfg, dtype="float32")
    mesh = make_host_mesh(pods=cohorts)
    case = _semi_sync_case(model_cfg, mesh, cohorts, seq_len, per_cohort,
                           seed)
    key = jax.random.PRNGKey(seed)
    state0 = jax.jit(case.init)(key)
    inputs = [(_random_batches(case, model_cfg.vocab_size,
                               jax.random.fold_in(key, 1000 + i)),
               _mask(i, cohorts), jax.random.fold_in(key, i + 1))
              for i in range(steps)]

    with jax.default_matmul_precision("highest"):
        # reference: one device, no mesh (sharding constraints are no-ops)
        ref_step = jax.jit(case.fn)
        ref = state0
        for b, m, k in inputs:
            ref, _ = ref_step(ref, b, m, k)

        with sharding.use_mesh(mesh, arch_rules(model_cfg, mesh)):
            step = jax.jit(case.fn, in_shardings=case.in_shardings,
                           out_shardings=case.out_shardings)
            got = jax.device_put(state0, case.in_shardings[0])
            for b, m, k in inputs:
                got, _ = step(got, jax.device_put(b, case.in_shardings[1]),
                              m, k)

    def deltas(state):
        return [np.asarray(p, np.float32) - np.asarray(p0, np.float32)
                for p, p0 in zip(jax.tree.leaves(state.params),
                                 jax.tree.leaves(state0.params))]

    d_got, d_ref = deltas(got), deltas(ref)
    check(all(bool(np.isfinite(d).all()) for d in d_got),
          f"{case.name}: non-finite parameters across {cohorts} devices")
    update = max(float(np.max(np.abs(d))) for d in d_ref)
    diff = max(float(np.max(np.abs(g - w))) for g, w in zip(d_got, d_ref))
    check(update > 0.0, f"{case.name}: parameters did not move")
    check(diff <= rtol * update,
          f"{case.name}: update across {cohorts} devices differs from one "
          f"device by {diff} (update max {update})")
    out = {"case": case.name, "steps": steps, "update_max_abs": update,
           "max_abs_diff": diff, "devices": len(mesh.devices.flat)}
    log(f"semi_sync/compare: {out}")
    return out


def semi_sync_run(model_cfg, *, cohorts: int = 4, seq_len: int,
                  per_cohort: int, steps: int = 3, seed: int = 0) -> dict:
    """A few semi-sync steps with one cohort per device."""
    mesh = make_host_mesh(pods=cohorts)
    case = _semi_sync_case(model_cfg, mesh, cohorts, seq_len, per_cohort,
                           seed)
    key = jax.random.PRNGKey(seed)
    with sharding.use_mesh(mesh, arch_rules(model_cfg, mesh)):
        compiled, compile_s = _compile(case)
        state = jax.jit(case.init, out_shardings=case.in_shardings[0])(key)
        hist = []
        for i in range(steps):
            b = _random_batches(case, model_cfg.vocab_size,
                                jax.random.fold_in(key, 1000 + i),
                                case.in_shardings[1])
            jax.block_until_ready(b)
            t0 = time.perf_counter()
            state, metrics = compiled(state, b, _mask(i, cohorts),
                                      jax.random.fold_in(key, i + 1))
            pnorm = float(tree_norm(state.params))
            rec = {"step": i, "grad_norm": float(metrics["grad_norm"]),
                   "participants": float(metrics["participants"]),
                   "param_norm": pnorm,
                   "step_s": time.perf_counter() - t0}
            check(_finite(rec["grad_norm"]) and _finite(pnorm),
                  f"{case.name} step {i}: {rec}")
            hist.append(rec)
            log(f"semi_sync/{case.name}: {rec}")
    out = {"case": case.name, "compile_s": compile_s, "steps": hist,
           "peak_bytes_in_use": [peak_bytes(d) for d in mesh.devices.flat]}
    log(f"semi_sync: compile_s={compile_s:.1f} "
        f"peak_bytes_in_use={out['peak_bytes_in_use']}")
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def mamba2_cut(num_layers: int):
    """mamba2_370m at published widths, depth cut to ``num_layers``."""
    cfg = get_config("mamba2_370m")
    return dataclasses.replace(cfg, num_layers=num_layers,
                               name=f"{cfg.name}-L{num_layers}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the semi-sync path across four chips")
    args = ap.parse_args(argv)

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev['platform']!r});"
              " nothing was run", file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" JAX sees {dev['count']}", file=sys.stderr)
        return 2
    log(f"device: {dev}; compile cache: {enable_compile_cache()}")

    t_all = time.perf_counter()
    if args.chips == 4:
        semi_sync_compare(mamba2_cut(4), cohorts=4, seq_len=512,
                          per_cohort=2)
        semi_sync_run(get_config("mamba2_370m"), cohorts=4,
                      seq_len=TRAIN_SEQ, per_cohort=TRAIN_BATCH)
    else:
        simulator_phase()
        train_phase(get_config("mamba2_370m"))
    log(f"total_s={time.perf_counter() - t_all:.1f}")
    print(json.dumps({"ok": True, "device": device_info()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
