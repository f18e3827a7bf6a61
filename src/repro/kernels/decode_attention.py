"""Single-token decode attention kernel (TPU Pallas target, interpret-
validated): one query row against a (ring-buffer) KV cache.

This is the serving hot spot — per generated token the whole cache streams
through VMEM once.  Blockwise online softmax over the cache-sequence axis:

  grid = (batch·q_heads, num_s_blocks)

The ring buffer's validity/window logic uses the cached absolute positions
(pos < 0 = empty slot), identical to the model's ``_attn_scores_mask``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

DEFAULT_BLOCK_S = 512
NEG_INF = -1e30


def _decode_kernel(qpos_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, window: int,
                   num_s_blocks: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                    # [1, D]
    k = k_ref[0].astype(jnp.float32)                    # [bs, D]
    v = v_ref[0].astype(jnp.float32)                    # [bs, D]
    pos = pos_ref[0]                                    # [bs] int32
    q_pos = qpos_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))[0] * scale  # [bs]
    mask = (pos >= 0) & (pos <= q_pos)
    if window > 0:
        mask &= (q_pos - pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[0]
    m_cur = jnp.maximum(m_prev, jnp.max(s))
    p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)        # [bs]
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[0] = alpha * l_ref[0] + jnp.sum(p)
    acc_ref[...] = acc_ref[...] * alpha + (p[:, None] * v).sum(
        axis=0, keepdims=True)
    m_ref[0] = m_cur

    @pl.when(si == num_s_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[0], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array,
                          pos: jax.Array, q_pos: jax.Array, *,
                          window: int = 0, scale: Optional[float] = None,
                          block_s: int = DEFAULT_BLOCK_S,
                          interpret: Optional[bool] = None) -> jax.Array:
    """q [B, Hq, D]; k/v [B, Hkv, S, D]; pos [B, S]; q_pos [B] → [B, Hq, D]."""
    b, hq, d = q.shape
    hkv, s_len = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    block_s = min(block_s, s_len)
    s_pad = -(-s_len // block_s) * block_s
    if s_pad != s_len:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, s_pad - s_len), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, s_pad - s_len), (0, 0)))
        pos = jnp.pad(pos, ((0, 0), (0, s_pad - s_len)), constant_values=-1)
    ns = s_pad // block_s

    qf = q.reshape(b * hq, 1, d)
    kf = k.reshape(b * hkv, s_pad, d)
    vf = v.reshape(b * hkv, s_pad, d)

    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               num_s_blocks=ns)
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, ns),
        in_specs=[
            pl.BlockSpec((1,), lambda bh, si: (bh // hq,)),
            pl.BlockSpec((1, 1, d), lambda bh, si: (bh, 0, 0)),
            pl.BlockSpec((1, block_s, d),
                         lambda bh, si: ((bh // hq) * hkv + (bh % hq) // group,
                                         si, 0)),
            pl.BlockSpec((1, block_s, d),
                         lambda bh, si: ((bh // hq) * hkv + (bh % hq) // group,
                                         si, 0)),
            pl.BlockSpec((1, block_s), lambda bh, si: (bh // hq, si)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda bh, si: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q_pos.astype(jnp.int32), qf, kf, vf, pos.astype(jnp.int32))
    return out.reshape(b, hq, d)
