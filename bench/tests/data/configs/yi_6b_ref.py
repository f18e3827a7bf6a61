"""Plain float32 reference of a Llama-style decoder (Yi-6B's layout).

Written from the published equations: RMSNorm before attention and before
the MLP, rotary position embedding on queries and keys (the halves of each
head rotated as a pair), causal grouped-query attention (query head i reads
key-value head i·K/H), a SwiGLU MLP, residual connections, and an untied
output head.  It imports nothing of the program, and gives what
``bench/harness.py`` asks of a reference: ``layout``, ``init`` and
``loss``.  Each layer is rematerialised.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from bench.eq7_ref import HIGHEST, nest

Tree = Dict[str, Any]


def as_run(cfg: dict, key: str):
    dep = cfg.get("departures", {}).get(key)
    return cfg[key] if dep is None else dep["run"]


def _sizes(cfg: dict) -> Tuple[int, int, int, int, int]:
    """(width, query heads, key-value heads, head size, MLP width)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, h, cfg["num_key_value_heads"], d // h, cfg["intermediate_size"]


def layout(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Path → (shape, dtype) of every parameter the step is given."""
    d, h, k, hd, f = _sizes(cfg)
    depth, vocab, dt = cfg["num_hidden_layers"], cfg["vocab_size"], \
        cfg["dtype"]
    out = {
        "embedding/tok_embed": ((vocab, d), dt),
        "final_norm/scale": ((d,), dt),
        "layers/norm_attn/scale": ((depth, d), dt),
        "layers/norm_ffn/scale": ((depth, d), dt),
        "layers/attn/w_q": ((depth, d, h * hd), dt),
        "layers/attn/w_k": ((depth, d, k * hd), dt),
        "layers/attn/w_v": ((depth, d, k * hd), dt),
        "layers/attn/w_o": ((depth, h * hd, d), dt),
        "layers/w_gate": ((depth, d, f), dt),
        "layers/w_up": ((depth, d, f), dt),
        "layers/w_down": ((depth, f, d), dt),
    }
    if not cfg["tie_word_embeddings"]:
        out["embedding/lm_head"] = ((d, vocab), dt)
    return out


def init(key, cfg: dict) -> Tree:
    """Random weights from ``key``: matrices N(0, 1/fan_in), the output
    projections of each layer also over sqrt(2·layers), the embedding
    N(0, 0.02), norm scales 1.  Jit it: it runs on the device in one call."""
    lay = layout(cfg)
    keys = dict(zip(sorted(lay), jax.random.split(key, len(lay))))
    deep = math.sqrt(2 * cfg["num_hidden_layers"])
    out = {}
    for path, (shape, dt) in lay.items():
        leaf = path.split("/")[-1]
        normal = jax.random.normal(keys[path], shape, jnp.float32)
        if leaf == "scale":
            v = jnp.ones(shape, jnp.float32)
        elif leaf == "tok_embed":
            v = 0.02 * normal
        else:
            v = normal / math.sqrt(shape[-2])
            if leaf in ("w_o", "w_down"):
                v = v / deep
        out[path] = v.astype(dt)
    return nest(out)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    """x [B, T, heads, D]: channel pair (j, j + D/2) turned by t·θ^(−2j/D)."""
    t, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(pl: Tree, x, cfg: dict, q: Callable, prec=HIGHEST):
    _, h, k, hd, _ = _sizes(cfg)
    eps = as_run(cfg, "rms_norm_eps")
    b, t, _ = x.shape
    mm = functools.partial(jnp.matmul, precision=prec)
    ein = functools.partial(jnp.einsum, precision=prec)
    at = pl["attn"]
    xn = q(_rmsnorm(x, pl["norm_attn"]["scale"], eps))
    qh = _rope(mm(xn, at["w_q"]).reshape(b, t, h, hd), cfg["rope_theta"])
    kh = _rope(mm(xn, at["w_k"]).reshape(b, t, k, hd), cfg["rope_theta"])
    vh = mm(xn, at["w_v"]).reshape(b, t, k, hd)
    kh, vh = (jnp.repeat(v, h // k, axis=2) for v in (kh, vh))
    s = ein("bqhd,bkhd->bhqk", q(qh), q(kh)) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = ein("bhqk,bkhd->bqhd", q(jax.nn.softmax(s, -1)), q(vh))
    x = x + mm(q(o.reshape(b, t, h * hd)), at["w_o"])
    xn = q(_rmsnorm(x, pl["norm_ffn"]["scale"], eps))
    g = jax.nn.silu(mm(xn, pl["w_gate"])) * mm(xn, pl["w_up"])
    return x + mm(q(g), pl["w_down"])


def loss(params: Tree, tokens, targets, cfg: dict, q: Callable = None,
         prec=HIGHEST, act: Callable = None):
    """Mean next-token cross-entropy of float32 ``params``.  ``act``
    rounds the residual stream after the embedding and each layer."""
    q = q or (lambda v: v)
    act = act or (lambda v: v)
    emb = params["embedding"]
    x = act(emb["tok_embed"][tokens])
    layer = jax.checkpoint(functools.partial(_layer, cfg=cfg, q=q,
                                             prec=prec))
    x, _ = jax.lax.scan(lambda xc, pl: (act(layer(pl, xc)), None), x,
                        params["layers"])
    x = _rmsnorm(x, params["final_norm"]["scale"],
                 as_run(cfg, "rms_norm_eps"))
    head = emb["lm_head"] if "lm_head" in emb else emb["tok_embed"].T
    logits = jnp.matmul(q(x), head, precision=prec)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)
