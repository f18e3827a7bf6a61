"""Abstract input/state specs for lowering (ShapeDtypeStruct — no allocation).

``build_case(arch, shape, mesh, ...)`` returns everything ``dryrun.py`` needs:
the jittable step function, abstract arguments, and in/out shardings.

Sharding policy (resolved per-arch by divisibility):
  params        2-D sharded by repro.sharding rules (feature→model, embed→data)
  batch dims    → ("pod","data")
  decode caches → heads→model if divisible else seq→model; batch→data if
                  divisible else left whole
  semi-sync cohort buffers → cohort axis on "pod"
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import sharding
from repro.config import (ExperimentConfig, FLConfig, ModelConfig,
                          ShapeConfig, TrainConfig)
from repro.core import semi_sync
from repro.models import build_model
from repro.optim import make_optimizer


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _divides(n: int, k: int) -> bool:
    return k > 0 and n > 0 and n % k == 0


def arch_rules(cfg: ModelConfig, mesh: Mesh) -> sharding.AxisRules:
    """Per-arch rule overrides driven by divisibility constraints."""
    rules = sharding.AxisRules()
    msize = mesh.shape.get("model", 1)
    over = {}
    if cfg.moe is not None and not _divides(cfg.moe.num_experts, msize):
        # too few experts for the model axis (mixtral 8e on 16): let the
        # expert FFN dim take the model axis instead (dense-TP style)
        over["experts"] = ()
    if cfg.vocab_size and not _divides(cfg.vocab_size, msize):
        over["vocab"] = ()
    if over:
        rules = rules.with_overrides(**over)
    return rules


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Physical axes carrying the batch dim — honours the active rule set
    (pure-DP setups map batch over the model axis too)."""
    cand = sharding.active_rules().rules.get("batch", ("pod", "data"))
    return tuple(a for a in cand if a in mesh.axis_names)


# ---------------------------------------------------------------------------
# abstract batches
# ---------------------------------------------------------------------------

def train_batch_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                      *, triplet: bool = True, n_cohorts: int = 0):
    """Abstract train batch (the Eq.-7 triplet), plus NamedShardings."""
    b, s = shape.global_batch, shape.seq_len
    lead = (n_cohorts, b // max(n_cohorts, 1)) if n_cohorts else (b,)
    tok_shape = lead + (s,)
    if cfg.family == "audio":
        tok_shape = tok_shape + (cfg.num_audio_codebooks,)

    def one_batch():
        d = {"tokens": _sds(tok_shape, jnp.int32),
             "targets": _sds(tok_shape, jnp.int32)}
        if cfg.family == "vlm":
            img = lead + (cfg.num_image_tokens, cfg.d_model)
            d["image_embeds"] = _sds(img, jnp.dtype(cfg.dtype))
        return d

    batch = ({"inner": one_batch(), "outer": one_batch(),
              "hessian": one_batch()} if triplet else one_batch())

    ba = batch_axes(mesh)
    if n_cohorts:
        # cohort → pod, per-cohort batch → data
        def spec_for(leaf):
            rest = (None,) * (len(leaf.shape) - 2)
            return NamedSharding(mesh, P("pod", "data", *rest))
    else:
        def spec_for(leaf):
            rest = (None,) * (len(leaf.shape) - 1)
            return NamedSharding(mesh, P(ba, *rest))
    shardings = jax.tree.map(spec_for, batch)
    return batch, shardings


def decode_inputs_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    b = shape.global_batch
    tok_shape = (b, 1) if cfg.family != "audio" \
        else (b, 1, cfg.num_audio_codebooks)
    tokens = _sds(tok_shape, jnp.int32)
    pos = _sds((), jnp.int32)
    ba = batch_axes(mesh)
    tok_spec = NamedSharding(
        mesh, P(ba, *([None] * (len(tok_shape) - 1)))) \
        if _divides(b, int(np.prod([mesh.shape[a] for a in ba]))) \
        else NamedSharding(mesh, P(*([None] * len(tok_shape))))
    return tokens, pos, tok_spec, NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# cache sharding
# ---------------------------------------------------------------------------

def cache_shardings(cache_abs, mesh: Mesh, batch: int,
                    policy: str = "auto"):
    """Assign NamedShardings to an abstract cache pytree by leaf path.

    ``policy="replicate"``: keep the whole cache replicated — for tiny-batch
    long-context decode this trades per-device memory for ZERO cache
    collectives (§Perf lever for the collective-bound long_500k cases).
    """
    dsize = mesh.shape.get("data", 1)
    msize = mesh.shape.get("model", 1)
    batch_ok = _divides(batch, dsize)
    if policy == "replicate":
        return jax.tree.map(
            lambda leaf: NamedSharding(mesh, P(*([None] * leaf.ndim))),
            cache_abs)

    def assign(path, leaf):
        name = sharding._path_str(path)
        dims: list = [None] * leaf.ndim
        # layout conventions (see models/*.init_cache):
        #   k/v   [L, B, S, H, D]      pos [L, B, S]
        #   ckv   [L, B, S, R]         kr  [L, B, S, R]
        #   conv  [L, B, W, C]         state [L, B, H, P, N]   h [L, B, W]
        if leaf.ndim >= 2 and batch_ok:
            dims[1] = "data"
        key = name.split("/")[-1]
        if key in ("k", "v") and leaf.ndim == 5:
            if _divides(leaf.shape[3], msize):
                dims[3] = "model"
            elif _divides(leaf.shape[2], msize):
                dims[2] = "model"
        elif key in ("ckv", "kr") and leaf.ndim == 4:
            if _divides(leaf.shape[2], msize):
                dims[2] = "model"
        elif key == "pos":
            pass
        elif key == "conv" and leaf.ndim == 4:
            if _divides(leaf.shape[3], msize):
                dims[3] = "model"
        elif key == "state" and leaf.ndim == 5:
            if _divides(leaf.shape[2], msize):
                dims[2] = "model"
            elif _divides(leaf.shape[3], msize):
                dims[3] = "model"
        elif key == "h" and leaf.ndim == 3:
            if _divides(leaf.shape[2], msize):
                dims[2] = "model"
        return NamedSharding(mesh, P(*dims))

    return jax.tree_util.tree_map_with_path(assign, cache_abs)


# ---------------------------------------------------------------------------
# state sharding
# ---------------------------------------------------------------------------

def state_shardings(state_abs, params_shardings, mesh: Mesh):
    """Shardings for TrainState / SemiSyncState given the params' shardings."""
    def like_params(tree):
        return tree

    if isinstance(state_abs, semi_sync.SemiSyncState):
        # buffers: cohort leading dim → pod, rest like params
        def buf_spec(ps):
            spec = ps.spec if isinstance(ps, NamedSharding) else P()
            lead = "pod" if "pod" in mesh.axis_names else None
            return NamedSharding(mesh, P(lead, *spec))
        buf_sh = jax.tree.map(buf_spec, params_shardings)
        opt_sh = _opt_shardings(state_abs.opt_state, params_shardings, mesh)
        return semi_sync.SemiSyncState(
            params=params_shardings,
            opt_state=opt_sh,
            buffers=buf_sh,
            staleness=NamedSharding(mesh, P(None)),
            step=NamedSharding(mesh, P()),
        )
    # TrainState
    opt_sh = _opt_shardings(state_abs.opt_state, params_shardings, mesh)
    return semi_sync.TrainState(
        params=params_shardings,
        opt_state=opt_sh,
        step=NamedSharding(mesh, P()),
    )


def _opt_shardings(opt_abs, params_shardings, mesh: Mesh):
    if isinstance(opt_abs, tuple) and len(opt_abs) == 0:
        return ()
    out = {}
    for key, sub in opt_abs.items():
        if key in ("m", "v"):
            out[key] = params_shardings
        else:
            out[key] = jax.tree.map(lambda _: NamedSharding(mesh, P()), sub)
    return out


# ---------------------------------------------------------------------------
# case builder
# ---------------------------------------------------------------------------

class LowerCase(NamedTuple):
    name: str
    fn: Callable            # jittable
    args: Tuple             # abstract args
    in_shardings: Tuple
    out_shardings: Any
    meta: Dict[str, Any]
    init: Optional[Callable] = None   # train: rng → concrete args[0] (state)


def build_case(model_cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
               fl: Optional[FLConfig] = None,
               train: Optional[TrainConfig] = None,
               moe_impl: str = "gather",
               semi_sync_cohorts: Optional[int] = None,
               perfed_step: bool = True,
               cache_policy: str = "auto",
               rules: Optional[sharding.AxisRules] = None,
               seed: int = 0) -> LowerCase:
    """Assemble one (arch × shape × mesh) lowering case."""
    fl = fl or FLConfig()
    train = train or TrainConfig(seq_len=shape.seq_len,
                                 global_batch_size=shape.global_batch)
    cfg = dataclasses.replace(model_cfg, max_seq_len=max(model_cfg.max_seq_len,
                                                         shape.seq_len))
    exp = ExperimentConfig(model=cfg, fl=fl, train=train)
    model = build_model(cfg, moe_impl=moe_impl)
    rules = rules or arch_rules(cfg, mesh)

    rng = jax.random.PRNGKey(seed)
    with sharding.use_mesh(None):   # abstract init never needs the mesh
        params_abs = jax.eval_shape(model.init, rng)
    pspecs = sharding.param_specs(params_abs, mesh, rules)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda s: isinstance(s, P))

    meta = {"arch": cfg.name, "shape": shape.name, "mesh": dict(mesh.shape),
            "kind": shape.kind}

    if shape.kind == "train":
        optimizer = make_optimizer("sgd")   # Alg.-1 server = β-SGD (faithful)
        if semi_sync_cohorts and semi_sync_cohorts > 1:
            step = semi_sync.make_semi_sync_step(model, exp, optimizer,
                                                 semi_sync_cohorts)
            init = functools.partial(semi_sync.init_state, model,
                                     optimizer=optimizer,
                                     n_cohorts=semi_sync_cohorts)
            state_abs = jax.eval_shape(init, rng)
            batch_abs, batch_sh = train_batch_specs(
                cfg, shape, mesh, triplet=True, n_cohorts=semi_sync_cohorts)
            mask_abs = _sds((semi_sync_cohorts,), jnp.float32)
            args = (state_abs, batch_abs, mask_abs, rng)
            st_sh = state_shardings(state_abs, psh, mesh)
            in_sh = (st_sh, batch_sh, NamedSharding(mesh, P(None)),
                     NamedSharding(mesh, P()))
            out_sh = (st_sh, jax.tree.map(
                lambda _: NamedSharding(mesh, P()),
                {"grad_norm": 0, "participants": 0, "max_staleness": 0}))
            name = f"{cfg.name}:{shape.name}:semi_sync"
        else:
            step = semi_sync.make_train_step(model, exp, optimizer,
                                             perfed_step=perfed_step)
            init = functools.partial(semi_sync.init_train_state, model,
                                     optimizer=optimizer)
            state_abs = jax.eval_shape(init, rng)
            batch_abs, batch_sh = train_batch_specs(cfg, shape, mesh,
                                                    triplet=True)
            args = (state_abs, batch_abs, rng)
            st_sh = state_shardings(state_abs, psh, mesh)
            in_sh = (st_sh, batch_sh, NamedSharding(mesh, P()))
            out_sh = (st_sh, {"loss": NamedSharding(mesh, P()),
                              "grad_norm": NamedSharding(mesh, P())})
            name = f"{cfg.name}:{shape.name}:perfed" if perfed_step \
                else f"{cfg.name}:{shape.name}:plain"
        return LowerCase(name, step, args, in_sh, out_sh, meta, init)

    if shape.kind == "prefill":
        batch_abs, batch_sh = train_batch_specs(cfg, shape, mesh,
                                                triplet=False)
        cache_len = min(shape.seq_len, _cache_len(cfg, shape))

        def prefill_fn(params, tokens, image_embeds=None):
            kw = {}
            if cfg.family == "vlm":
                kw["image_embeds"] = image_embeds
            return model.prefill(params, tokens, cache_len, **kw)

        args = [params_abs, batch_abs["tokens"]]
        in_sh = [psh, batch_sh["tokens"]]
        if cfg.family == "vlm":
            args.append(batch_abs["image_embeds"])
            in_sh.append(batch_sh["image_embeds"])
        cache_abs = jax.eval_shape(
            lambda p, t, *i: prefill_fn(p, t, *i)[1], *args)
        csh = cache_shardings(cache_abs, mesh, shape.global_batch)
        ba = batch_axes(mesh)
        logit_sh = NamedSharding(mesh, P(ba, None, None)) \
            if cfg.family != "audio" else NamedSharding(mesh, P(ba, None, None, None))
        out_sh = (logit_sh, csh)
        return LowerCase(f"{cfg.name}:{shape.name}:prefill", prefill_fn,
                         tuple(args), tuple(in_sh), out_sh, meta)

    # decode
    tokens_abs, pos_abs, tok_sh, pos_sh = decode_inputs_specs(cfg, shape, mesh)
    cache_len = _cache_len(cfg, shape)
    window = _decode_window(cfg, shape)
    cache_abs = jax.eval_shape(
        functools.partial(model.init_cache, shape.global_batch, cache_len))
    csh = cache_shardings(cache_abs, mesh, shape.global_batch,
                          policy=cache_policy)

    def decode_fn(params, cache, tokens, pos):
        kw = {"window": window} if window is not None else {}
        if cfg.family == "vlm":
            kw["image_embeds"] = _vlm_img_abs(cfg, shape.global_batch)
        return model.decode_step(params, cache, tokens, pos, **kw)

    if cfg.family == "vlm":
        def decode_fn(params, cache, tokens, pos, img):  # noqa: F811
            kw = {"window": window} if window is not None else {}
            return model.decode_step(params, cache, tokens, pos,
                                     image_embeds=img, **kw)

    args = [params_abs, cache_abs, tokens_abs, pos_abs]
    in_sh = [psh, csh, tok_sh, pos_sh]
    if cfg.family == "vlm":
        img_abs = _sds((shape.global_batch, cfg.num_image_tokens, cfg.d_model),
                       jnp.dtype(cfg.dtype))
        args.append(img_abs)
        ba = batch_axes(mesh)
        bdim = ba if _divides(shape.global_batch,
                              int(np.prod([mesh.shape[a] for a in ba]))) else None
        in_sh.append(NamedSharding(mesh, P(bdim, None, None)))
    logit_sh = tok_sh if cfg.family != "audio" else NamedSharding(
        mesh, P(*tok_sh.spec, None))
    out_sh = (NamedSharding(mesh, P(*((None,) * (2 if cfg.family != "audio"
                                                 else 3)))), csh)
    return LowerCase(f"{cfg.name}:{shape.name}:decode", decode_fn,
                     tuple(args), tuple(in_sh), out_sh, meta)


def _cache_len(cfg: ModelConfig, shape: ShapeConfig) -> int:
    """KV-cache length: full history for decode_32k; the sliding window for
    long_500k (sub-quadratic memory — full 524k cache is never materialised
    for attention archs; SSM/hybrid have O(1) state anyway)."""
    if cfg.family in ("ssm",):
        return 0
    if cfg.sliding_window:
        return min(shape.seq_len, cfg.sliding_window)
    if shape.seq_len > 65536:
        return cfg.long_context_window
    return shape.seq_len


def _decode_window(cfg: ModelConfig, shape: ShapeConfig) -> Optional[int]:
    if cfg.family in ("ssm", "hybrid"):
        return None
    if cfg.sliding_window:
        return None                      # model already windows natively
    if shape.seq_len > 65536:
        return cfg.long_context_window   # sliding-window long-context variant
    return None


def _vlm_img_abs(cfg, batch):
    return _sds((batch, cfg.num_image_tokens, cfg.d_model),
                jnp.dtype(cfg.dtype))
