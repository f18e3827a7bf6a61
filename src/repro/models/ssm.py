"""Mamba-2 (SSD — state-space duality) stack. [arXiv:2405.21060]

Training/prefill uses the chunked SSD algorithm (chunk-local quadratic term +
inter-chunk linear state recurrence); decode is the O(1)/token recurrent step.
Attention-free: the natural sub-quadratic citizen for ``long_500k``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.config import ModelConfig
from repro.models import layers as L

Params = Dict[str, Any]


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    nheads = s.num_heads or d_inner // s.head_dim
    return d_inner, nheads, s.head_dim, s.state_dim


def segsum(x: jax.Array) -> jax.Array:
    """Stable 'segment sum': out[..., i, j] = sum_{j < m <= i} x[..., m].

    Returns -inf above the diagonal (used as log-decay matrix L).
    """
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, chunk: int, initial_state: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    x  [B, L, H, P]   inputs (per-head channels)
    dt [B, L, H]      positive step sizes
    a  [H]            negative per-head decay rates
    b  [B, L, N]      input projections (shared across heads, G=1)
    c  [B, L, N]      output projections
    Returns (y [B, L, H, P], final_state [B, H, P, N]).
    """
    bs, sl, h, p = x.shape
    n = b.shape[-1]
    l_orig = sl
    if sl % chunk:
        # zero-pad to a chunk multiple: dt=0 at pads ⇒ decay 1, update 0 —
        # the state is provably unaffected by padding positions
        pad = chunk - sl % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
        sl = sl + pad
    nc = sl // chunk

    xr = x.reshape(bs, nc, chunk, h, p)
    dtr = dt.reshape(bs, nc, chunk, h)
    br = b.reshape(bs, nc, chunk, n)
    cr = c.reshape(bs, nc, chunk, n)
    da = dtr * a                                                     # [B,NC,Q,H] (<0)
    da = jnp.moveaxis(da, -1, -2)                                    # [B,NC,H,Q]

    # 1) intra-chunk (quadratic within the chunk)
    lmat = jnp.exp(segsum(da))                                       # [B,NC,H,Q,Q]
    scores = jnp.einsum("bzin,bzjn->bzij", cr, br)                   # [B,NC,Q,Q]
    xdt = xr * dtr[..., None]                                        # x * dt
    y_intra = jnp.einsum("bzij,bzhij,bzjhp->bzihp", scores, lmat, xdt)

    # 2) chunk summaries: decay from step j to end of chunk = exp(sum_{m>j} da_m)
    cum = jnp.cumsum(da, axis=-1)                                    # [B,NC,H,Q]
    decay_end = jnp.exp(cum[..., -1:] - cum)                         # [B,NC,H,Q]
    states = jnp.einsum("bzjn,bzhj,bzjhp->bzhpn", br, decay_end, xdt)

    # 3) inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(cum[..., -1])                              # [B,NC,H]
    s0 = (initial_state if initial_state is not None
          else jnp.zeros((bs, h, p, n), x.dtype))

    def step(s_prev, inp):
        dec, st = inp
        s_new = s_prev * dec[..., None, None] + st
        return s_new, s_prev

    (s_final, s_prevs) = jax.lax.scan(
        step, s0.astype(jnp.float32),
        (jnp.moveaxis(chunk_decay, 1, 0).astype(jnp.float32),
         jnp.moveaxis(states, 1, 0).astype(jnp.float32)))
    s_prevs = jnp.moveaxis(s_prevs, 0, 1)                            # [B,NC,H,P,N]

    # 4) contribution of previous-chunk state to each position
    in_decay = jnp.exp(cum)                              # decay from chunk start
    y_inter = jnp.einsum("bzin,bzhi,bzhpn->bzihp", cr, in_decay,
                         s_prevs.astype(cr.dtype))

    y = (y_intra + y_inter).reshape(bs, sl, h, p)[:, :l_orig]
    return y.astype(x.dtype), s_final.astype(x.dtype)


def ssd_step(state: jax.Array, x: jax.Array, dt: jax.Array, a: jax.Array,
             b: jax.Array, c: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Single recurrent step. state [B,H,P,N]; x [B,H,P]; dt [B,H]; b,c [B,N]."""
    da = jnp.exp(dt * a)                                             # [B,H]
    upd = jnp.einsum("bhp,bn->bhpn", x * dt[..., None], b)
    state = state * da[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", state, c)
    return state, y


class Mamba2LM:
    def __init__(self, cfg: ModelConfig, moe_impl: str = "gather"):
        self.cfg = cfg

    # ------------------------------------------------------------- init ---
    def _layer_init(self, key) -> Params:
        cfg = self.cfg
        d_inner, h, p, n = _dims(cfg)
        dt = L._dt(cfg)
        conv_dim = d_inner + 2 * n
        ks = jax.random.split(key, 4)
        proj_out = 2 * d_inner + 2 * n + h                           # z, x, B, C, dt
        return {
            "norm_attn": L.rmsnorm_init(cfg.d_model, dt),
            "in_proj": L.dense_init(ks[0], cfg.d_model, proj_out, dt),
            "conv_w": (jax.random.normal(ks[1], (cfg.ssm.conv_width, conv_dim),
                                         jnp.float32) / math.sqrt(cfg.ssm.conv_width)
                       ).astype(dt),
            "conv_b": jnp.zeros((conv_dim,), dt),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, h).astype(jnp.float32)),
            "dt_bias": jnp.zeros((h,), jnp.float32),
            "D_skip": jnp.ones((h,), jnp.float32),
            "norm_gate": L.rmsnorm_init(d_inner, dt),
            "out_proj": L.dense_init(ks[2], d_inner, cfg.d_model, dt,
                                     scale=1.0 / math.sqrt(d_inner * cfg.num_layers)),
        }

    def init(self, rng) -> Params:
        cfg = self.cfg
        k_e, k_l = jax.random.split(rng)
        return {
            "embedding": L.embedding_init(k_e, cfg),
            "final_norm": L.rmsnorm_init(cfg.d_model, L._dt(cfg)),
            "layers": jax.vmap(self._layer_init)(
                jax.random.split(k_l, cfg.num_layers)),
        }

    # -------------------------------------------------------- internals ---
    def _split_proj(self, zxbcdt):
        cfg = self.cfg
        d_inner, h, p, n = _dims(cfg)
        z = zxbcdt[..., :d_inner]
        xbc = zxbcdt[..., d_inner:2 * d_inner + 2 * n]
        dt_raw = zxbcdt[..., 2 * d_inner + 2 * n:]
        return z, xbc, dt_raw

    def _layer_train(self, pl: Params, x: jax.Array) -> jax.Array:
        """Full-sequence SSD mixing for one layer."""
        cfg = self.cfg
        d_inner, h, p, n = _dims(cfg)
        resid = x
        with jax.named_scope("ssm.in_proj"):
            xn = L.rmsnorm(pl["norm_attn"], x)
            z, xbc, dt_raw = self._split_proj(xn @ pl["in_proj"])
        with jax.named_scope("ssm.conv"):
            # causal depthwise conv (width W): pad left
            w = cfg.ssm.conv_width
            pad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
            conv = sum(pad[:, i:i + xbc.shape[1], :]
                       * pl["conv_w"][i][None, None, :]
                       for i in range(w)) + pl["conv_b"]
            xbc = jax.nn.silu(conv)
            xs = xbc[..., :d_inner].reshape(x.shape[0], x.shape[1], h, p)
            b = xbc[..., d_inner:d_inner + n]
            c = xbc[..., d_inner + n:]
        with jax.named_scope("ssm.ssd"):
            dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + pl["dt_bias"])
            a = -jnp.exp(pl["A_log"])
            y, _ = ssd_chunked(xs.astype(jnp.float32), dt, a,
                               b.astype(jnp.float32), c.astype(jnp.float32),
                               cfg.ssm.chunk_size)
            y = y + pl["D_skip"][None, None, :, None] * xs.astype(jnp.float32)
        with jax.named_scope("ssm.out_proj"):
            y = y.reshape(x.shape[0], x.shape[1], d_inner).astype(x.dtype)
            y = L.rmsnorm(pl["norm_gate"], y * jax.nn.silu(z))
            return resid + y @ pl["out_proj"]

    # --------------------------------------------------------- forward ----
    def forward(self, params: Params, tokens: jax.Array, **_kw):
        cfg = self.cfg
        x = L.embed(params["embedding"], tokens)
        x = sharding.constrain(x, "batch", None, None)

        def body(xc, pl):
            f = self._layer_train
            if cfg.remat:
                f = jax.checkpoint(f)
            return f(pl, xc), 0

        x, _ = jax.lax.scan(body, x, params["layers"])
        with jax.named_scope("ssm.head"):
            x = L.rmsnorm(params["final_norm"], x)
            logits = L.unembed(params["embedding"], x)
        return logits, None, jnp.zeros((), jnp.float32)

    def loss(self, params, batch, rng=None):
        logits, _, _ = self.forward(params, batch["tokens"])
        with jax.named_scope("ssm.head"):
            ce = L.cross_entropy(logits, batch["targets"], batch.get("mask"))
        return ce, {"ce": ce}

    def predict(self, params, batch):
        return self.forward(params, batch["tokens"])[0]

    # ------------------------------------------------------- serving ------
    def init_cache(self, batch: int, cache_len: int = 0) -> Params:
        """Recurrent cache: conv tail + SSM state per layer (cache_len unused —
        state is O(1) in sequence length)."""
        cfg = self.cfg
        d_inner, h, p, n = _dims(cfg)
        conv_dim = d_inner + 2 * n
        dt = L._dt(cfg)
        return {
            "conv": jnp.zeros((cfg.num_layers, batch, cfg.ssm.conv_width - 1,
                               conv_dim), dt),
            "state": jnp.zeros((cfg.num_layers, batch, h, p, n), dt),
        }

    def _layer_step(self, pl: Params, lc: Params, x: jax.Array
                    ) -> Tuple[jax.Array, Params]:
        cfg = self.cfg
        d_inner, h, p, n = _dims(cfg)
        resid = x
        xn = L.rmsnorm(pl["norm_attn"], x)                           # [B,1,d]
        z, xbc, dt_raw = self._split_proj(xn @ pl["in_proj"])
        xbc1 = xbc[:, 0, :]                                          # [B,convdim]
        hist = jnp.concatenate([lc["conv"], xbc1[:, None, :]], axis=1)
        conv = jnp.einsum("bwc,wc->bc", hist, pl["conv_w"]) + pl["conv_b"]
        new_conv = hist[:, 1:, :]
        u = jax.nn.silu(conv)
        xs = u[:, :d_inner].reshape(-1, h, p)
        b = u[:, d_inner:d_inner + n]
        c = u[:, d_inner + n:]
        dt = jax.nn.softplus(dt_raw[:, 0, :].astype(jnp.float32) + pl["dt_bias"])
        a = -jnp.exp(pl["A_log"])
        state, y = ssd_step(lc["state"].astype(jnp.float32),
                            xs.astype(jnp.float32), dt, a,
                            b.astype(jnp.float32), c.astype(jnp.float32))
        y = y + pl["D_skip"][None, :, None] * xs.astype(jnp.float32)
        y = y.reshape(-1, 1, d_inner).astype(x.dtype)
        y = L.rmsnorm(pl["norm_gate"], y * jax.nn.silu(z))
        out = resid + y @ pl["out_proj"]
        return out, {"conv": new_conv.astype(lc["conv"].dtype),
                     "state": state.astype(lc["state"].dtype)}

    def prefill(self, params: Params, tokens: jax.Array, cache_len: int = 0,
                **_kw) -> Tuple[jax.Array, Params]:
        """Prefill = full SSD pass that also materialises the recurrent cache."""
        cfg = self.cfg
        d_inner, h, p, n = _dims(cfg)
        x = L.embed(params["embedding"], tokens)
        bsz, lq = tokens.shape

        def body(xc, pl):
            resid = xc
            xn = L.rmsnorm(pl["norm_attn"], xc)
            z, xbc, dt_raw = self._split_proj(xn @ pl["in_proj"])
            w = cfg.ssm.conv_width
            pad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
            conv = sum(pad[:, i:i + lq, :] * pl["conv_w"][i][None, None, :]
                       for i in range(w)) + pl["conv_b"]
            conv_tail = pad[:, -(w - 1):, :] if w > 1 else pad[:, :0, :]
            u = jax.nn.silu(conv)
            xs = u[..., :d_inner].reshape(bsz, lq, h, p)
            b = u[..., d_inner:d_inner + n]
            c = u[..., d_inner + n:]
            dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + pl["dt_bias"])
            a = -jnp.exp(pl["A_log"])
            y, s_final = ssd_chunked(xs.astype(jnp.float32), dt, a,
                                     b.astype(jnp.float32), c.astype(jnp.float32),
                                     cfg.ssm.chunk_size)
            y = y + pl["D_skip"][None, None, :, None] * xs.astype(jnp.float32)
            y = y.reshape(bsz, lq, d_inner).astype(xc.dtype)
            y = L.rmsnorm(pl["norm_gate"], y * jax.nn.silu(z))
            out = resid + y @ pl["out_proj"]
            return out, {"conv": conv_tail.astype(xc.dtype),
                         "state": s_final.astype(xc.dtype)}

        x, cache = jax.lax.scan(body, x, params["layers"])
        x = L.rmsnorm(params["final_norm"], x)
        logits = L.unembed(params["embedding"], x[:, -1:])
        return logits, cache

    def decode_step(self, params: Params, cache: Params, tokens: jax.Array,
                    pos: jax.Array, **_kw) -> Tuple[jax.Array, Params]:
        x = L.embed(params["embedding"], tokens)                     # [B,1,d]

        def body(xc, xs):
            pl, lc = xs
            out, new_lc = self._layer_step(pl, lc, xc)
            return out, new_lc

        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
        x = L.rmsnorm(params["final_norm"], x)
        logits = L.unembed(params["embedding"], x)
        return logits, new_cache
