"""Semi-synchronous aggregation as a first-class SPMD training feature.

This is the datacenter-scale mapping of Alg. 1: each *cohort* (= one pod of
the multi-pod mesh, or a slice of the data axis) plays the role of a UE.  The
server's "wait for A of n" becomes a **masked psum across the cohort axis**;
gradients "in flight" live in a per-cohort buffer carried in the train state
(sharded over the cohort axis so each pod keeps exactly one extra gradient).

Per step (round k), given the Alg.-2 schedule mask π_k:

  1. w_{k+1} = w_k − β/A · Σ_{i: π_i=1} buf_i          (Eq. 8 — arriving grads,
     possibly computed against w_{k−τ_i}: that's exactly what the buffer holds)
  2. refresh: cohorts with π_i=1 (or staleness > S) compute a fresh PerFed
     meta-gradient (Eq. 7) against w_{k+1} and overwrite their buffer slot
  3. staleness counters advance; the simulator (fl/simulation.py) decides the
     masks and wall-clock times — this module is pure SPMD math.

With n_cohorts=1 and π=[1] this degenerates exactly to synchronous
Per-FedAvg (the paper's PerFed-SYN baseline) — used for the single-pod
roofline profile.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.config import ExperimentConfig
from repro.core import perfed
from repro.kernels.stale_aggregate import (masked_aggregate_tree,
                                           stale_aggregate_tree)
from repro.optim import Optimizer, clip_by_global_norm


class SemiSyncState(NamedTuple):
    params: Any                  # meta model w_k
    opt_state: Any               # server optimizer state (empty for β-SGD)
    buffers: Any                 # per-cohort pending grads [n_cohorts, ...]
    staleness: jax.Array         # [n_cohorts] int32 — rounds since last refresh
    step: jax.Array              # round counter k


def init_state(model, rng, optimizer: Optimizer, n_cohorts: int
               ) -> SemiSyncState:
    params = model.init(rng)
    buffers = jax.tree.map(
        lambda p: jnp.zeros((n_cohorts,) + p.shape, p.dtype), params)
    return SemiSyncState(
        params=params,
        opt_state=optimizer.init(params),
        buffers=buffers,
        staleness=jnp.zeros((n_cohorts,), jnp.int32),
        step=jnp.zeros((), jnp.int32),
    )


def _cohort_grads(model, cfg: ExperimentConfig, params, cohort_batches,
                  rng) -> Any:
    """PerFed meta-gradient per cohort: vmap over the leading cohort dim.

    ``cohort_batches`` = {"inner": ..., "outer": ..., "hessian": ...} with
    each leaf shaped [n_cohorts, B_c, ...].
    """
    fl = cfg.fl

    def one(batches, r):
        if fl.algorithm == "perfed":
            return perfed.perfed_grad(model.loss, params, batches, fl.alpha,
                                      first_order=fl.first_order, rng=r)
        # fedavg-style plain gradient on the union batch
        def scalar(p):
            out = model.loss(p, batches["outer"], r)
            return out[0] if isinstance(out, tuple) else out
        return jax.grad(scalar)(params)

    n = jax.tree.leaves(cohort_batches)[0].shape[0]
    rngs = jax.random.split(rng, n)
    return jax.vmap(one, in_axes=(0, 0))(cohort_batches, rngs)


def uses_fused_eq8(optimizer: Optimizer, cfg: ExperimentConfig) -> bool:
    """Pure Eq. (8) — β-SGD, no clipping — is exactly the fused masked
    stale-aggregation op; anything fancier needs the masked mean first."""
    return optimizer.name == "sgd" and not cfg.train.grad_clip


def make_semi_sync_step(model, cfg: ExperimentConfig, optimizer: Optimizer,
                        n_cohorts: int) -> Callable:
    """Build the jittable semi-synchronous round function.

    step(state, cohort_batches, mask, rng) -> (state, metrics)
      mask: float [n_cohorts] — π_k (1 = this cohort's gradient arrives now)
    """
    fl = cfg.fl

    fused_eq8 = uses_fused_eq8(optimizer, cfg)

    def step_fn(state: SemiSyncState, cohort_batches, mask: jax.Array, rng
                ) -> Tuple[SemiSyncState, Dict[str, jax.Array]]:
        # -- 1) server update from arriving (possibly stale) gradients -------
        # via the unified aggregation API (same code path as the simulation
        # server and the engine's fused round / Pallas kernel)
        with jax.named_scope("semi_sync.eq8"):
            if fused_eq8:
                gnorm = jnp.zeros(())
                new_params = stale_aggregate_tree(state.params, state.buffers,
                                                  mask, beta=fl.beta)
                new_opt = state.opt_state
            else:
                agg = masked_aggregate_tree(state.buffers, mask)
                if cfg.train.grad_clip:
                    agg, gnorm = clip_by_global_norm(agg, cfg.train.grad_clip)
                else:
                    gnorm = jnp.zeros(())
                new_params, new_opt = optimizer.update(agg, state.opt_state,
                                                       state.params, fl.beta)

        # -- 2) refresh buffers: scheduled cohorts (+ over-stale ones) -------
        refresh = (mask > 0) | (state.staleness > fl.staleness_bound)
        fresh = _cohort_grads(model, cfg, new_params, cohort_batches, rng)
        new_buffers = jax.tree.map(
            lambda buf, fg: jnp.where(
                refresh.reshape((-1,) + (1,) * (buf.ndim - 1)),
                fg.astype(buf.dtype), buf),
            state.buffers, fresh)

        # -- 3) staleness bookkeeping ----------------------------------------
        new_staleness = jnp.where(refresh, 0, state.staleness + 1)

        metrics = {
            "grad_norm": gnorm,
            "participants": mask.sum(),
            "max_staleness": new_staleness.max(),
        }
        return SemiSyncState(new_params, new_opt, new_buffers,
                             new_staleness.astype(jnp.int32),
                             state.step + 1), metrics

    return step_fn


# ---------------------------------------------------------------------------
# Plain train step (non-FL baseline / dry-run compute profile)
# ---------------------------------------------------------------------------

class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array


def init_train_state(model, rng, optimizer: Optimizer) -> TrainState:
    params = model.init(rng)
    return TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32))


def make_train_step(model, cfg: ExperimentConfig, optimizer: Optimizer,
                    *, perfed_step: bool = True) -> Callable:
    """Single-cohort training step.

    ``perfed_step=True`` → the paper-faithful Per-FedAvg step (inner adapt +
    outer grad + HVP correction, Eq. 7) — this is what the roofline profiles.
    ``False`` → plain LM gradient step (the FedAvg / standard baseline).
    """
    fl = cfg.fl

    def step_fn(state: TrainState, batches, rng
                ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        if perfed_step:
            loss, grads = perfed.perfed_value_and_grad(
                model.loss, state.params, batches, fl.alpha,
                first_order=fl.first_order, rng=rng)
        else:
            def scalar(p):
                out = model.loss(p, batches["outer"], rng)
                return out[0] if isinstance(out, tuple) else out
            loss, grads = jax.value_and_grad(scalar)(state.params)
        with jax.named_scope("train.update"):
            if cfg.train.grad_clip:
                grads, gnorm = clip_by_global_norm(grads, cfg.train.grad_clip)
            else:
                gnorm = jnp.zeros(())
            lr = fl.beta if perfed_step else cfg.train.learning_rate
            new_params, new_opt = optimizer.update(grads, state.opt_state,
                                                   state.params, lr)
        return TrainState(new_params, new_opt, state.step + 1), {
            "loss": loss, "grad_norm": gnorm}

    return step_fn
