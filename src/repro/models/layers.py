"""Shared neural-net layers (pure-functional, pytree params).

Conventions
-----------
* Params are nested dicts with descriptive key names; ``repro.sharding``
  resolves PartitionSpecs from those names (see ``_PARAM_RULES``).
* Activations flow in ``cfg.dtype`` (bf16 by default); softmax/norm statistics
  accumulate in f32.
* Decode caches are dicts of arrays with static shapes.  Sliding-window caches
  are ring buffers storing absolute positions, so the same attention code
  handles full, windowed and ring-buffer caches uniformly.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sharding
from repro.config import ModelConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    w = jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale
    return w.astype(dtype)


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# normalisation
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias_ln": jnp.zeros((d,), dtype)}


def layernorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias_ln"].astype(jnp.float32)
    return y.astype(x.dtype)


def make_norm(cfg: ModelConfig):
    if cfg.norm == "layernorm":
        return layernorm_init, layernorm
    return rmsnorm_init, rmsnorm


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., L, H, D] (D even), positions: broadcastable to [..., L]."""
    d = x.shape[-1]
    assert d % 2 == 0, "rope head_dim must be even"
    freqs = jnp.exp(-jnp.arange(0, d, 2, dtype=jnp.float32) / d * math.log(theta))
    ang = positions.astype(jnp.float32)[..., None] * freqs          # [..., L, D/2]
    cos = jnp.cos(ang)[..., None, :]                                # [..., L, 1, D/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention core (shared by GQA / MLA / cross / local)
# ---------------------------------------------------------------------------

def _attn_scores_mask(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
                      window: int) -> jax.Array:
    """Boolean mask [.., Lq, Lk]; k_pos < 0 marks invalid (ring-buffer hole)."""
    valid = k_pos >= 0
    m = valid[..., None, :]
    if causal:
        m = m & (k_pos[..., None, :] <= q_pos[..., :, None])
    if window:
        m = m & (q_pos[..., :, None] - k_pos[..., None, :] < window)
    return m


SDPA_CHUNK = 1024   # q-chunk length for the memory-efficient path


def _sdpa_block(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
                scale: float, cast_f32: bool = True) -> jax.Array:
    """One q-block of attention. q [B,Lq,Hq,D], k/v [B,Lk,Hkv,Dk/Dv],
    mask [B,Lq,Lk].

    ``cast_f32=False`` keeps k/v in their storage dtype and requests f32
    accumulation from the MXU (``preferred_element_type``) instead of
    materialising an f32 copy of the whole cache — §Perf memory lever.
    """
    b, lq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, lq, hkv, g, d)
    if cast_f32:
        qg, k, v = (x.astype(jnp.float32) for x in (qg, k, v))
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, lq, hq, v.shape[-1]).astype(q.dtype)


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, *,
         q_pos: jax.Array, k_pos: jax.Array, causal: bool, window: int,
         scale: Optional[float] = None, chunk: int = SDPA_CHUNK,
         cast_f32: bool = True) -> jax.Array:
    """Scaled dot-product attention with GQA head-group broadcast.

    Memory-efficient: when Lq > ``chunk`` the query axis is processed in
    chunks via ``lax.map`` so the [Lq, Lk] score matrix is never fully
    materialised (required for the 32k-prefill shapes).

    q: [B, Lq, Hq, D], k/v: [B, Lk, Hkv, D].
    q_pos [B, Lq], k_pos [B, Lk] — absolute positions; k_pos < 0 = invalid.
    """
    b, lq, hq, d = q.shape
    assert hq % k.shape[2] == 0
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    if lq <= chunk:
        mask = _attn_scores_mask(q_pos, k_pos, causal=causal, window=window)
        return _sdpa_block(q, k, v, mask, scale, cast_f32)

    n_chunks = -(-lq // chunk)
    pad = n_chunks * chunk - lq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-1)
    qs = q.reshape(b, n_chunks, chunk, hq, d).swapaxes(0, 1)
    qp = q_pos.reshape(b, n_chunks, chunk).swapaxes(0, 1)

    def one(args):
        qc, qpc = args
        mask = _attn_scores_mask(qpc, k_pos, causal=causal, window=window)
        mask &= (qpc >= 0)[..., :, None]
        return _sdpa_block(qc, k, v, mask, scale, cast_f32)

    out = jax.lax.map(one, (qs, qp))
    out = out.swapaxes(0, 1).reshape(b, n_chunks * chunk, hq, v.shape[-1])
    return out[:, :lq]


def attention_init(key, cfg: ModelConfig, *, cross: bool = False) -> Params:
    dt = _dt(cfg)
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "w_q": dense_init(kq, cfg.d_model, cfg.num_heads * hd, dt),
        "w_k": dense_init(kk, cfg.d_model, cfg.num_kv_heads * hd, dt),
        "w_v": dense_init(kv, cfg.d_model, cfg.num_kv_heads * hd, dt),
        "w_o": dense_init(ko, cfg.num_heads * hd, cfg.d_model, dt,
                          scale=1.0 / math.sqrt(
                              cfg.num_heads * hd * 2 * cfg.num_layers)),
    }


def init_kv_cache(cfg: ModelConfig, batch: int, cache_len: int,
                  num_layers: Optional[int] = None, *, stacked: bool = True) -> Params:
    """Ring-buffer KV cache. ``pos`` holds absolute positions (-1 = empty)."""
    dt = _dt(cfg)
    hd = cfg.resolved_head_dim
    nl = num_layers if num_layers is not None else cfg.num_layers
    lead = (nl,) if stacked else ()
    return {
        "k": jnp.zeros(lead + (batch, cache_len, cfg.num_kv_heads, hd), dt),
        "v": jnp.zeros(lead + (batch, cache_len, cfg.num_kv_heads, hd), dt),
        "pos": -jnp.ones(lead + (batch, cache_len), jnp.int32),
    }


def attention_apply(params: Params, x: jax.Array, *, cfg: ModelConfig,
                    positions: jax.Array,
                    cache: Optional[Params] = None,
                    kv_input: Optional[jax.Array] = None,
                    causal: bool = True,
                    window: int = 0) -> Tuple[jax.Array, Optional[Params]]:
    """Unified attention.

    * train/prefill: ``cache is None`` or to-be-filled; ``x`` is [B, L, d].
    * decode:        ``cache`` holds past K/V; ``x`` is [B, 1, d].
    * cross:         ``kv_input`` supplies K/V source (no causal mask).

    Returns (out [B, L, d], updated cache or None).
    """
    b, lq, _ = x.shape
    hd = cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads

    q = (x @ params["w_q"]).reshape(b, lq, hq, hd)
    src = kv_input if kv_input is not None else x
    lk_new = src.shape[1]
    k = (src @ params["w_k"]).reshape(b, lk_new, hkv, hd)
    v = (src @ params["w_v"]).reshape(b, lk_new, hkv, hd)

    if kv_input is None and cfg.attention != "none":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions if cache is None else positions, cfg.rope_theta)
    q = sharding.constrain(q, "batch", None, "act_heads", None)

    new_cache = None
    if cache is not None:
        # write new k/v into the ring buffer at slot = pos % W; when prefilling
        # more than W tokens, only the last W writes are kept (drop the rest so
        # duplicate slots never race).
        w = cache["k"].shape[1]
        pos_b = jnp.broadcast_to(positions, (lq,)).astype(jnp.int32)
        keep = pos_b >= (pos_b[-1] - w + 1)
        slots = jnp.where(keep, pos_b % w, w)                       # w = OOB → dropped
        slots = jnp.broadcast_to(slots, (b, lq))
        bidx = jnp.arange(b)[:, None]
        ck = cache["k"].at[bidx, slots].set(k.astype(cache["k"].dtype), mode="drop")
        cv = cache["v"].at[bidx, slots].set(v.astype(cache["v"].dtype), mode="drop")
        cpos = cache["pos"].at[bidx, slots].set(
            jnp.broadcast_to(pos_b, (b, lq)), mode="drop")
        new_cache = {"k": ck, "v": cv, "pos": cpos}
        q_pos = jnp.broadcast_to(positions, (b, lq))
        if lq == 1:
            # decode: attend against the cache contents
            k, v, k_pos = ck, cv, cpos
        else:
            # prefill: attend within the fresh sequence (the ring buffer may
            # only retain the last W entries; outputs need the full window
            # relative to each query position)
            k_pos = q_pos
    else:
        q_pos = jnp.broadcast_to(positions, (b, lq))
        if kv_input is not None:
            k_pos = jnp.zeros((b, lk_new), jnp.int32)               # dense cross
            causal, window = False, 0
        else:
            k_pos = q_pos

    if cfg.attn_impl == "pallas" and cache is None and kv_input is None:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=causal, window=window)
    else:
        out = sdpa(q, k, v, q_pos=q_pos, k_pos=k_pos, causal=causal,
                   window=window, cast_f32=cfg.attn_cast_f32)
    out = out.reshape(b, lq, hq * hd) @ params["w_o"]
    return out, new_cache


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig) -> Params:
    m = cfg.mla
    dt = _dt(cfg)
    ks = jax.random.split(key, 6)
    h = cfg.num_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    p: Params = {
        "w_dkv": dense_init(ks[0], cfg.d_model, m.kv_lora_rank, dt),
        "w_kr":  dense_init(ks[1], cfg.d_model, m.qk_rope_head_dim, dt),
        "w_uk":  dense_init(ks[2], m.kv_lora_rank, h * m.qk_nope_head_dim, dt),
        "w_uv":  dense_init(ks[3], m.kv_lora_rank, h * m.v_head_dim, dt),
        "w_o":   dense_init(ks[4], h * m.v_head_dim, cfg.d_model, dt,
                            scale=1.0 / math.sqrt(
                                h * m.v_head_dim * 2 * cfg.num_layers)),
        "norm_ckv": rmsnorm_init(m.kv_lora_rank, dt),
    }
    if m.q_lora_rank:
        kq1, kq2 = jax.random.split(ks[5])
        p["w_dq"] = dense_init(kq1, cfg.d_model, m.q_lora_rank, dt)
        p["w_uq"] = dense_init(kq2, m.q_lora_rank, h * qk_head, dt)
        p["norm_q"] = rmsnorm_init(m.q_lora_rank, dt)
    else:
        p["w_q"] = dense_init(ks[5], cfg.d_model, h * qk_head, dt)
    return p


def init_mla_cache(cfg: ModelConfig, batch: int, cache_len: int,
                   num_layers: Optional[int] = None) -> Params:
    """MLA latent cache: per position store c_kv [rank] + rotary key [rope_dim]."""
    m = cfg.mla
    dt = _dt(cfg)
    nl = num_layers if num_layers is not None else cfg.num_layers
    return {
        "ckv": jnp.zeros((nl, batch, cache_len, m.kv_lora_rank), dt),
        "kr": jnp.zeros((nl, batch, cache_len, m.qk_rope_head_dim), dt),
        "pos": -jnp.ones((nl, batch, cache_len), jnp.int32),
    }


def mla_apply(params: Params, x: jax.Array, *, cfg: ModelConfig,
              positions: jax.Array, cache: Optional[Params] = None,
              window: int = 0) -> Tuple[jax.Array, Optional[Params]]:
    """MLA attention; decode path uses the *absorbed* formulation against the
    latent cache (the memory saving that motivates MLA)."""
    m = cfg.mla
    b, lq, _ = x.shape
    h = cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    if m.q_lora_rank:
        q = rmsnorm(params["norm_q"], x @ params["w_dq"]) @ params["w_uq"]
    else:
        q = x @ params["w_q"]
    q = q.reshape(b, lq, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = rmsnorm(params["norm_ckv"], x @ params["w_dkv"])          # [B, L, rank]
    kr = (x @ params["w_kr"])[:, :, None, :]                        # [B, L, 1, dr]
    kr = apply_rope(kr, positions, cfg.rope_theta)[:, :, 0, :]      # [B, L, dr]

    scale = 1.0 / math.sqrt(dn + dr)

    new_cache = None
    if cache is not None:
        w = cache["ckv"].shape[1]
        pos_b = jnp.broadcast_to(positions, (lq,)).astype(jnp.int32)
        keep = pos_b >= (pos_b[-1] - w + 1)
        slots = jnp.broadcast_to(jnp.where(keep, pos_b % w, w), (b, lq))
        bidx = jnp.arange(b)[:, None]
        cckv = cache["ckv"].at[bidx, slots].set(
            ckv.astype(cache["ckv"].dtype), mode="drop")
        ckr = cache["kr"].at[bidx, slots].set(
            kr.astype(cache["kr"].dtype), mode="drop")
        cpos = cache["pos"].at[bidx, slots].set(
            jnp.broadcast_to(pos_b, (b, lq)), mode="drop")
        new_cache = {"ckv": cckv, "kr": ckr, "pos": cpos}

    if cache is not None and lq == 1:
        # absorbed decode: score = q_nope·(W_uk c) + q_rope·k_r
        #                = (q_nope W_uk^T)·c + q_rope·k_r
        cast = (lambda x: x.astype(jnp.float32)) if cfg.attn_cast_f32 \
            else (lambda x: x)
        w_uk = params["w_uk"].reshape(m.kv_lora_rank, h, dn)
        q_lat = jnp.einsum("bqhd,rhd->bqhr", cast(q_nope), cast(w_uk),
                           preferred_element_type=jnp.float32)      # [B,Lq,H,rank]
        s_lat = jnp.einsum("bqhr,bkr->bhqk", q_lat.astype(cckv.dtype),
                           cast(cckv), preferred_element_type=jnp.float32)
        s_rope = jnp.einsum("bqhd,bkd->bhqk", cast(q_rope), cast(ckr),
                            preferred_element_type=jnp.float32)
        logits = (s_lat + s_rope) * scale
        q_pos = jnp.broadcast_to(positions, (b, lq))
        mask = _attn_scores_mask(q_pos, cpos, causal=True, window=window)
        logits = jnp.where(mask[:, None, :, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        # out_h = probs · v = probs · (W_uv c): aggregate latent then up-project
        lat = jnp.einsum("bhqk,bkr->bqhr", probs.astype(cckv.dtype) if not
                         cfg.attn_cast_f32 else probs, cast(cckv),
                         preferred_element_type=jnp.float32)
        w_uv = params["w_uv"].reshape(m.kv_lora_rank, h, dv)
        out = jnp.einsum("bqhr,rhd->bqhd", lat.astype(w_uv.dtype) if not
                         cfg.attn_cast_f32 else lat, cast(w_uv),
                         preferred_element_type=jnp.float32)
        out = out.astype(x.dtype).reshape(b, lq, h * dv) @ params["w_o"]
        return out, new_cache

    # train / prefill: materialise k/v heads (standard formulation)
    k_nope = (ckv @ params["w_uk"]).reshape(b, lq, h, dn)
    vh = (ckv @ params["w_uv"]).reshape(b, lq, h, dv)
    kh = jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, :, None, :], (b, lq, h, dr))],
                         axis=-1)
    qh = jnp.concatenate([q_nope, q_rope], axis=-1)
    q_pos = jnp.broadcast_to(positions, (b, lq))
    out = sdpa(qh, kh, vh, q_pos=q_pos, k_pos=q_pos, causal=True,
               window=window, scale=scale, cast_f32=cfg.attn_cast_f32)
    out = out.reshape(b, lq, h * dv) @ params["w_o"]
    return out, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def _act(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "sq_relu":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name!r}")


def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None,
             prefix: str = "") -> Params:
    dt = _dt(cfg)
    f = d_ff or cfg.d_ff
    gated = cfg.activation in ("silu", "gelu")
    ks = jax.random.split(key, 3)
    p = {
        prefix + "w_up": dense_init(ks[0], cfg.d_model, f, dt),
        prefix + "w_down": dense_init(ks[1], f, cfg.d_model, dt,
                                      scale=1.0 / math.sqrt(f * 2 * cfg.num_layers)),
    }
    if gated:
        p[prefix + "w_gate"] = dense_init(ks[2], cfg.d_model, f, dt)
    return p


def mlp_apply(params: Params, x: jax.Array, cfg: ModelConfig,
              prefix: str = "") -> jax.Array:
    act = _act(cfg.activation)
    up = x @ params[prefix + "w_up"]
    if prefix + "w_gate" in params:
        h = act(x @ params[prefix + "w_gate"]) * up
    else:
        h = act(up)
    h = sharding.constrain(h, "batch", None, "act_ffn")
    return h @ params[prefix + "w_down"]


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig) -> Params:
    e = cfg.moe
    dt = _dt(cfg)
    f = e.expert_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    d = cfg.d_model
    sc_in = 1.0 / math.sqrt(d)
    sc_out = 1.0 / math.sqrt(f * 2 * cfg.num_layers)

    def expert_bank(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    p: Params = {
        "router": dense_init(ks[0], d, e.num_experts, jnp.float32, scale=sc_in),
        "moe_gate": expert_bank(ks[1], (e.num_experts, d, f), sc_in),
        "moe_up": expert_bank(ks[2], (e.num_experts, d, f), sc_in),
        "moe_down": expert_bank(ks[3], (e.num_experts, f, d), sc_out),
    }
    if e.num_shared_experts:
        fs = f * e.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared_gate"] = dense_init(k1, d, fs, dt, scale=sc_in)
        p["shared_up"] = dense_init(k2, d, fs, dt, scale=sc_in)
        p["shared_down"] = dense_init(k3, fs, d, dt, scale=sc_out)
    return p


def _route(params: Params, xf: jax.Array, e) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing.  xf: [T, d] f32.  Returns (probs [T,k], idx [T,k], aux)."""
    logits = xf @ params["router"]                                   # [T, E] f32
    full = jax.nn.softmax(logits, axis=-1)
    probs, idx = jax.lax.top_k(full, e.experts_per_token)
    probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss
    counts = jnp.zeros((e.num_experts,), jnp.float32)
    counts = counts.at[idx.reshape(-1)].add(1.0)
    frac_tokens = counts / jnp.maximum(counts.sum(), 1.0)
    frac_probs = full.mean(axis=0)
    aux = e.num_experts * jnp.sum(frac_tokens * frac_probs) * e.router_aux_loss_coef
    return probs, idx, aux


def moe_apply_gather(params: Params, x: jax.Array, cfg: ModelConfig
                     ) -> Tuple[jax.Array, jax.Array]:
    """Capacity-bucketed sort/gather MoE (single-host / GSPMD-auto path)."""
    e = cfg.moe
    b, sl, d = x.shape
    t = b * sl
    k = e.experts_per_token
    xf = x.reshape(t, d)
    probs, idx, aux = _route(params, xf.astype(jnp.float32), e)

    cap = int(math.ceil(t * k / e.num_experts * e.capacity_factor))
    cap = max(8, -(-cap // 8) * 8)                                   # round up to 8

    e_flat = idx.reshape(-1)                                         # [T*k]
    t_flat = jnp.repeat(jnp.arange(t), k)
    g_flat = probs.reshape(-1)
    order = jnp.argsort(e_flat, stable=True)
    se, st, sg = e_flat[order], t_flat[order], g_flat[order]
    starts = jnp.searchsorted(se, jnp.arange(e.num_experts))         # [E]
    slot = jnp.arange(t * k) - starts[se]
    ok = slot < cap
    dst = jnp.where(ok, se * cap + slot, e.num_experts * cap)        # overflow row

    buf = jnp.zeros((e.num_experts * cap + 1, d), x.dtype).at[dst].set(xf[st])
    h = buf[:-1].reshape(e.num_experts, cap, d)
    act = _act("silu")
    hg = jnp.einsum("ecd,edf->ecf", h, params["moe_gate"])
    hu = jnp.einsum("ecd,edf->ecf", h, params["moe_up"])
    ho = jnp.einsum("ecf,efd->ecd", act(hg) * hu, params["moe_down"])
    ho = jnp.concatenate([ho.reshape(e.num_experts * cap, d),
                          jnp.zeros((1, d), x.dtype)], axis=0)
    contrib = ho[dst] * (sg * ok).astype(x.dtype)[:, None]
    out = jnp.zeros((t, d), x.dtype).at[st].add(contrib)

    if e.num_shared_experts:
        out = out + _shared_expert(params, xf, cfg)
    return out.reshape(b, sl, d), aux


def _shared_expert(params: Params, xf: jax.Array, cfg: ModelConfig) -> jax.Array:
    act = _act("silu")
    h = act(xf @ params["shared_gate"]) * (xf @ params["shared_up"])
    return h @ params["shared_down"]


def moe_apply_ep(params: Params, x: jax.Array, cfg: ModelConfig
                 ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map: experts live on the ``model`` axis,
    tokens are replicated across it; each shard computes only its experts and
    contributions are combined with a single psum (beyond-GSPMD perf path)."""
    mesh = sharding.active_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return moe_apply_gather(params, x, cfg)
    e = cfg.moe
    b, sl, d = x.shape
    t_global = b * sl
    k = e.experts_per_token
    ep = mesh.shape["model"]

    w_gate, w_up, w_down = (params["moe_gate"], params["moe_up"],
                            params["moe_down"])
    f_dim = w_gate.shape[-1]
    # routing outside shard_map (cheap, lets GSPMD place the [T, E] matmul)
    probs, idx, aux = _route(params, x.reshape(t_global, d).astype(jnp.float32), e)

    if e.num_experts % ep == 0:
        rep = 1
        e_eff, k_eff = e.num_experts, k
    elif ep % e.num_experts == 0:
        # fewer experts than shards: split each expert's FFN width into
        # ``rep`` chunks → E·rep "virtual experts" (sum-decomposable: the
        # gated MLP is additive over f-chunks through w_down) so every
        # shard owns exactly one virtual expert
        rep = ep // e.num_experts
        assert f_dim % rep == 0
        e_eff, k_eff = e.num_experts * rep, k * rep
        fr = f_dim // rep
        w_gate = w_gate.reshape(e.num_experts, d, rep, fr) \
            .swapaxes(1, 2).reshape(e_eff, d, fr)
        w_up = w_up.reshape(e.num_experts, d, rep, fr) \
            .swapaxes(1, 2).reshape(e_eff, d, fr)
        w_down = w_down.reshape(e.num_experts, rep, fr, d) \
            .reshape(e_eff, fr, d)
        idx = (idx[..., None] * rep
               + jnp.arange(rep)[None, None, :]).reshape(t_global, k_eff)
        probs = jnp.repeat(probs, rep, axis=-1)
    else:
        raise ValueError(f"experts={e.num_experts} incompatible with "
                         f"model axis {ep}")
    e_loc = e_eff // ep

    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    x_spec = P(batch_axes, None, None)
    probs = probs.reshape(b, sl, k_eff)
    idx = idx.reshape(b, sl, k_eff)

    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= mesh.shape[a]
    t_loc = t_global // n_batch_shards
    cap = int(math.ceil(t_loc * k / e.num_experts * e.capacity_factor))
    cap = max(8, -(-cap // 8) * 8)

    def shard_fn(xb, pb, ib, wg, wu, wd):
        bb, ll, _ = xb.shape
        tl = bb * ll
        xl = xb.reshape(tl, d)
        pl = pb.reshape(tl * k_eff)
        il = ib.reshape(tl * k_eff)
        my = jax.lax.axis_index("model") * e_loc
        e_rel = il - my
        mine = (e_rel >= 0) & (e_rel < e_loc)
        sort_key = jnp.where(mine, e_rel, e_loc)     # sentinel e_loc = "not mine"
        order = jnp.argsort(sort_key, stable=True)
        se, sm = sort_key[order], mine[order]
        st = jnp.repeat(jnp.arange(tl), k_eff)[order]
        sg = pl[order]
        starts = jnp.searchsorted(se, jnp.arange(e_loc))
        slot = jnp.arange(tl * k_eff) - starts[jnp.clip(se, 0, e_loc - 1)]
        ok = sm & (slot < cap)
        dst = jnp.where(ok, jnp.clip(se, 0, e_loc - 1) * cap + slot, e_loc * cap)
        buf = jnp.zeros((e_loc * cap + 1, d), xb.dtype).at[dst].set(xl[st])
        h = buf[:-1].reshape(e_loc, cap, d)
        act = _act("silu")
        hg = jnp.einsum("ecd,edf->ecf", h, wg)
        hu = jnp.einsum("ecd,edf->ecf", h, wu)
        ho = jnp.einsum("ecf,efd->ecd", act(hg) * hu, wd)
        ho = jnp.concatenate([ho.reshape(e_loc * cap, d),
                              jnp.zeros((1, d), xb.dtype)], axis=0)
        contrib = ho[dst] * (sg * ok).astype(xb.dtype)[:, None]
        out = jnp.zeros((tl, d), xb.dtype).at[st].add(contrib)
        out = jax.lax.psum(out, "model")
        return out.reshape(bb, ll, d)

    out = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(x_spec, P(batch_axes, None, None), P(batch_axes, None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=x_spec,
        check_vma=False,
    )(x, probs.astype(x.dtype), idx, w_gate, w_up, w_down)

    if e.num_shared_experts:
        xf = x.reshape(t_global, d)
        out = out + _shared_expert(params, xf, cfg).reshape(b, sl, d)
    return out, aux


def moe_apply(params: Params, x: jax.Array, cfg: ModelConfig, impl: str = "gather"
              ) -> Tuple[jax.Array, jax.Array]:
    if impl == "ep":
        return moe_apply_ep(params, x, cfg)
    return moe_apply_gather(params, x, cfg)


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def embedding_init(key, cfg: ModelConfig) -> Params:
    dt = _dt(cfg)
    k1, k2 = jax.random.split(key)
    p = {"tok_embed": (jax.random.normal(k1, (cfg.vocab_size, cfg.d_model),
                                         jnp.float32) * 0.02).astype(dt)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(k2, cfg.d_model, cfg.vocab_size, dt)
    return p


def embed(params: Params, tokens: jax.Array) -> jax.Array:
    return params["tok_embed"][tokens]


def unembed(params: Params, x: jax.Array) -> jax.Array:
    if "lm_head" in params:
        return x @ params["lm_head"]
    return x @ params["tok_embed"].T


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token cross-entropy in f32. logits [..., V], targets [...] int."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
