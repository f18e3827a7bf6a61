"""Plain float32 reference of mamba2_370m: the model of the PerFedS² step.

Written from the Mamba-2 paper (arXiv:2405.21060: the block of Fig. 6 and
the minimal SSD algorithm of Listing 1).  It imports nothing of the
program.  ``bench/eq7_ref.py`` follows the step over ``loss``; the module
gives what ``bench/harness.py`` asks of a reference: ``layout``, ``init``
and ``loss``.

The SSD einsums run in float32 in the configuration, so under the float8
control (``q is round8``) their inputs are rounded to bfloat16, one
precision lower, and those of the dense products to float8 e4m3.  Each
layer is rematerialised.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from bench.eq7_ref import HIGHEST, nest, round8, round16

Tree = Dict[str, Any]


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def dims(cfg: dict) -> Tuple[int, int, int, int, int, int]:
    """(d_inner, heads, headdim, d_state, conv channels, in_proj width)."""
    d_inner = cfg["expand"] * cfg["d_model"]
    h = d_inner // cfg["headdim"]
    n = cfg["ngroups"] * cfg["d_state"]
    conv_dim = d_inner + 2 * n
    return d_inner, h, cfg["headdim"], n, conv_dim, conv_dim + d_inner + h


def vocab_rows(cfg: dict) -> int:
    """The vocabulary padded to ``pad_vocab_size_multiple`` (mamba_ssm)."""
    m = cfg.get("pad_vocab_size_multiple", 1)
    return -(-cfg["vocab_size"] // m) * m


def as_run(cfg: dict, key: str):
    """``cfg[key]`` as the program runs it: where the program departs from
    the source (``departures``), the value it runs."""
    dep = cfg.get("departures", {}).get(key)
    return cfg[key] if dep is None else dep["run"]


def layout(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Path → (shape, dtype) of every parameter the step is given."""
    d, depth, vocab = cfg["d_model"], cfg["n_layer"], vocab_rows(cfg)
    d_inner, h, _, _, conv_dim, proj = dims(cfg)
    dt = cfg["dtype"]
    out = {
        "embedding/tok_embed": ((vocab, d), dt),
        "final_norm/scale": ((d,), dt),
        "layers/norm_attn/scale": ((depth, d), dt),
        "layers/in_proj": ((depth, d, proj), dt),
        "layers/conv_w": ((depth, cfg["d_conv"], conv_dim), dt),
        "layers/conv_b": ((depth, conv_dim), dt),
        "layers/A_log": ((depth, h), "float32"),
        "layers/dt_bias": ((depth, h), "float32"),
        "layers/D_skip": ((depth, h), "float32"),
        "layers/norm_gate/scale": ((depth, d_inner), dt),
        "layers/out_proj": ((depth, d_inner, d), dt),
    }
    if not cfg["tie_embeddings"]:
        out["embedding/lm_head"] = ((d, vocab), dt)
    return out


def init(key, cfg: dict) -> Tree:
    """Random weights from ``key`` (the Mamba-2 initialisation), in the
    dtypes of ``layout``.  Jit it: it runs on the device in one call."""
    d = cfg["d_model"]
    d_inner = dims(cfg)[0]
    lay = layout(cfg)
    keys = dict(zip(sorted(lay), jax.random.split(key, len(lay))))
    out = {}
    for path, (shape, dt) in lay.items():
        k, leaf = keys[path], path.split("/")[-1]
        normal = jax.random.normal(k, shape, jnp.float32)
        if leaf == "scale" or leaf == "D_skip":
            v = jnp.ones(shape, jnp.float32)
        elif leaf == "tok_embed":
            v = 0.02 * normal
        elif leaf in ("in_proj", "lm_head"):
            v = normal / math.sqrt(d)
        elif leaf == "out_proj":
            v = normal / math.sqrt(d_inner * cfg["n_layer"])
        elif leaf == "conv_w":
            v = normal / math.sqrt(cfg["d_conv"])
        elif leaf == "conv_b":
            v = jnp.zeros(shape, jnp.float32)
        elif leaf == "A_log":
            v = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif leaf == "dt_bias":
            dt_ = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                             math.log(1e-3), math.log(1e-1)))
            v = dt_ + jnp.log(-jnp.expm1(-dt_))      # softplus⁻¹(dt)
        else:
            raise KeyError(path)
        out[path] = v.astype(dt)
    return nest(out)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _segsum(x):
    """out[..., i, j] = Σ_{j < m ≤ i} x[..., m]; −inf above the diagonal."""
    t = x.shape[-1]
    xe = jnp.broadcast_to(x[..., :, None], x.shape + (t,))
    xe = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), xe, 0.0)
    cs = jnp.cumsum(xe, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool), 0), cs, -jnp.inf)


def ssd(x, a, b, c, chunk: int, q: Callable, prec=HIGHEST):
    """Minimal SSD (Mamba-2 Listing 1).  x [B,T,H,P] (already × dt),
    a [B,T,H] (A·dt), b/c [B,T,N] (one group).  Returns y [B,T,H,P]."""
    bs, t, h, p = x.shape
    n = b.shape[-1]
    nc = t // chunk
    ein = functools.partial(jnp.einsum, precision=prec)
    x = q(x).reshape(bs, nc, chunk, h, p)
    b = q(b).reshape(bs, nc, chunk, n)
    c = q(c).reshape(bs, nc, chunk, n)
    a = a.reshape(bs, nc, chunk, h).transpose(0, 3, 1, 2)        # b h c l
    a_cs = jnp.cumsum(a, axis=-1)
    lmat = jnp.exp(_segsum(a))                                    # b h c l s
    y_diag = ein("bcln,bcsn,bhcls,bcshp->bclhp", c, b, lmat, x)
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs)
    states = ein("bcln,bhcl,bclhp->bchpn", b, decay_states, x)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(_segsum(jnp.pad(a_cs[..., -1],
                                          ((0, 0), (0, 0), (1, 0)))))
    states = ein("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    y_off = ein("bcln,bchpn,bhcl->bclhp", c, states, jnp.exp(a_cs))
    return (y_diag + y_off).reshape(bs, t, h, p)


def _layer(pl: Tree, x, cfg: dict, q: Callable, prec=HIGHEST):
    d_inner, h, p, n, conv_dim, _ = dims(cfg)
    eps = as_run(cfg, "norm_epsilon")
    bs, t, _ = x.shape
    mm = functools.partial(jnp.matmul, precision=prec)
    xn = _rmsnorm(x, pl["norm_attn"]["scale"], eps)
    zxbcdt = mm(q(xn), pl["in_proj"])
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + conv_dim]
    dt_raw = zxbcdt[..., d_inner + conv_dim:]
    k = cfg["d_conv"]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + t, :] * pl["conv_w"][i] for i in range(k))
    xbc = jax.nn.silu(conv + pl["conv_b"])
    xs = xbc[..., :d_inner].reshape(bs, t, h, p)
    bm = xbc[..., d_inner:d_inner + n]
    cm = xbc[..., d_inner + n:]
    dt = jax.nn.softplus(dt_raw + pl["dt_bias"])
    a = -jnp.exp(pl["A_log"])
    # the SSD runs in float32 in the configuration: its control is bf16
    q_ssd = round16 if q is round8 else q
    y = ssd(xs * dt[..., None], a * dt, bm, cm, cfg["chunk_size"], q_ssd,
            prec)
    y = y + pl["D_skip"][:, None] * xs
    y = _rmsnorm(y.reshape(bs, t, d_inner) * jax.nn.silu(z),
                 pl["norm_gate"]["scale"], eps)
    return x + mm(q(y), pl["out_proj"])


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
                 as_run(cfg, "norm_epsilon"))
    head = emb["lm_head"] if "lm_head" in emb else emb["tok_embed"].T
    logits = jnp.matmul(q(x), head, precision=prec)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)
