"""Serving launcher: batched prefill + decode with a (optionally personalized)
model.  Runs reduced configs for real on CPU; full configs lower via dryrun.

The PFL twist: ``--personalize`` adapts the served weights with one inner
SGD step on a provided "user" batch before serving — the deployment story of
Per-FedAvg (every user serves their own fine-tuned model from the meta
initialisation).

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch yi_6b --reduce \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser(description="batched serving driver")
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--reduce", action="store_true", default=True)
    ap.add_argument("--full", dest="reduce", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--personalize", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.perfed import adapt
    from repro.models import build_model

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = cfg.reduced()
    model = build_model(cfg)
    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng)
    b, lp = args.batch, args.prompt_len

    tok_shape = (b, lp) if cfg.family != "audio" \
        else (b, lp, cfg.num_audio_codebooks)
    prompts = jax.random.randint(rng, tok_shape, 0, cfg.vocab_size)

    if args.personalize:
        targ = jnp.roll(prompts, -1, axis=1)
        user_batch = {"tokens": prompts, "targets": targ}
        params = adapt(model.loss, params, user_batch, alpha=0.01, rng=rng)
        print("personalized: one inner-SGD adaptation step applied")

    prefill = jax.jit(lambda p, t: model.prefill(p, t, args.cache_len))
    decode = jax.jit(model.decode_step)

    t0 = time.time()
    logits, cache = prefill(params, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if cfg.family == "audio":
        toks = toks.reshape(b, 1, -1)
    else:
        toks = toks.reshape(b, 1)
    out_tokens = [toks]
    t0 = time.time()
    for i in range(args.gen - 1):
        logits, cache = decode(params, cache, toks, jnp.int32(lp + i))
        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks = toks.reshape(b, 1, -1) if cfg.family == "audio" \
            else toks.reshape(b, 1)
        out_tokens.append(toks)
    jax.block_until_ready(toks)
    t_decode = time.time() - t0

    gen = jnp.concatenate(out_tokens, axis=1)
    print(f"arch={cfg.name} batch={b} prompt={lp} gen={args.gen}")
    print(f"prefill: {t_prefill*1e3:.1f} ms   "
          f"decode: {t_decode/max(args.gen-1,1)*1e3:.2f} ms/token")
    print("sample tokens:", np.asarray(gen)[0].tolist()[:12])
    return 0


if __name__ == "__main__":
    sys.exit(main())
