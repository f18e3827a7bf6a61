"""End-to-end training launcher.

Two modes:

* ``--mode fl``     (default) — the paper: event-driven PerFedS² simulation
  over a mobile edge network with the paper's small models + synthetic
  federated datasets.  Runs for real on CPU.
* ``--mode scale``  — datacenter path: PerFed semi-sync step on an assigned
  LLM architecture over a device mesh (reduced sizes run on host devices;
  full sizes are exercised by ``dryrun.py``).

Examples:
  PYTHONPATH=src python -m repro.launch.train --mode fl --arch mnist_dnn \
      --algo perfed --sync-mode semi fl.rounds=50
  PYTHONPATH=src python -m repro.launch.train --mode scale --arch yi_6b \
      --reduce --steps 20
"""
from __future__ import annotations

import argparse
import sys
import time
from dataclasses import replace


def main(argv=None):
    ap = argparse.ArgumentParser(description="PerFedS² training launcher")
    ap.add_argument("--mode", default="fl", choices=["fl", "scale"])
    ap.add_argument("--arch", default="mnist_dnn")
    ap.add_argument("--algo", default="perfed",
                    choices=["perfed", "fedavg", "fedprox"])
    ap.add_argument("--sync-mode", default="semi",
                    choices=["sync", "semi", "async"])
    ap.add_argument("--bandwidth", default="optimal",
                    choices=["optimal", "equal"])
    ap.add_argument("--noniid-l", type=int, default=4)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--reduce", action="store_true",
                    help="scale mode: reduced model for CPU execution")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--metrics-dir", default="",
                    help="write metrics.jsonl under this directory")
    ap.add_argument("overrides", nargs="*", help="dotted config overrides")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.config import ExperimentConfig, apply_overrides, parse_cli_overrides
    from repro.configs import get_config

    cfg = ExperimentConfig(model=get_config(args.arch))
    cfg = apply_overrides(cfg, parse_cli_overrides(args.overrides))

    if args.mode == "fl":
        return run_fl(cfg, args)
    return run_scale(cfg, args)


def run_fl(cfg, args):
    import jax
    from repro.data import (partition_noniid, synthetic_cifar, synthetic_mnist,
                            synthetic_shakespeare)
    from repro.data.partition import sequence_clients
    from repro.fl.simulation import run_simulation
    from repro.models import build_model

    model = build_model(cfg.model)
    name = cfg.model.name
    if name.startswith("char_lstm"):
        role_data = synthetic_shakespeare(n_roles=cfg.fl.n_ues)
        clients = sequence_clients(role_data, cfg.fl.n_ues, seed=args.seed)
    elif name.startswith("lenet5"):
        data = synthetic_cifar(n=4000)
        clients = partition_noniid(data, cfg.fl.n_ues, n_labels=args.noniid_l,
                                   seed=args.seed)
    else:
        data = synthetic_mnist(n=4000)
        clients = partition_noniid(data, cfg.fl.n_ues, n_labels=args.noniid_l,
                                   seed=args.seed)

    res = run_simulation(cfg, model, clients, algorithm=args.algo,
                         mode=args.sync_mode, bandwidth_policy=args.bandwidth,
                         seed=args.seed, verbose=True)
    if args.metrics_dir:
        from repro.utils.metrics import MetricsLogger
        with MetricsLogger(args.metrics_dir,
                           meta={"arch": args.arch, "algo": args.algo,
                                 "mode": args.sync_mode}) as log:
            for i in range(len(res.times)):
                log.log(step=int(res.rounds[i]), sim_t=float(res.times[i]),
                        ploss=float(res.losses[i]),
                        gloss=float(res.global_losses[i]))
    print(f"\nfinal: t={res.total_time:.2f}s rounds={res.rounds[-1]} "
          f"personalized_loss={res.losses[-1]:.4f} "
          f"global_loss={res.global_losses[-1]:.4f} "
          f"wait_frac={res.wait_fraction:.3f}")
    return 0


def run_scale(cfg, args):
    import jax
    import jax.numpy as jnp
    from repro.checkpoint import save_checkpoint
    from repro.core import semi_sync
    from repro.models import build_model
    from repro.optim import make_optimizer

    mcfg = cfg.model.reduced() if args.reduce else cfg.model
    model = build_model(mcfg)
    optimizer = make_optimizer("sgd")
    step_fn = jax.jit(semi_sync.make_train_step(model, replace(cfg, model=mcfg),
                                                optimizer, perfed_step=True))
    rng = jax.random.PRNGKey(args.seed)
    state = semi_sync.init_train_state(model, rng, optimizer)

    from repro.data.synthetic import synthetic_lm_corpus
    corpus = synthetic_lm_corpus(n_tokens=1 << 15, vocab=mcfg.vocab_size)
    seq, bsz = 64, 8

    def batch(r):
        starts = jax.random.randint(r, (bsz,), 0, len(corpus) - seq - 1)
        toks = jnp.stack([jnp.asarray(corpus[s:s + seq]) for s in starts])
        targ = jnp.stack([jnp.asarray(corpus[s + 1:s + seq + 1]) for s in starts])
        if mcfg.family == "audio":
            toks = jnp.tile(toks[..., None] % mcfg.vocab_size,
                            (1, 1, mcfg.num_audio_codebooks))
            targ = jnp.tile(targ[..., None] % mcfg.vocab_size,
                            (1, 1, mcfg.num_audio_codebooks))
        return {"tokens": toks, "targets": targ}

    t0 = time.time()
    for step in range(args.steps):
        rng, r1, r2, r3, r4 = jax.random.split(rng, 5)
        batches = {"inner": batch(r1), "outer": batch(r2), "hessian": batch(r3)}
        state, metrics = step_fn(state, batches, r4)
        if step % max(1, args.steps // 10) == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"({time.time()-t0:.1f}s)", flush=True)
    if args.ckpt_dir:
        f = save_checkpoint(args.ckpt_dir, state.params, step=args.steps)
        print("saved", f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
