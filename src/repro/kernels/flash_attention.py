"""Blockwise flash attention (TPU Pallas target; interpreted off the TPU).

Online-softmax attention with explicit VMEM tiling via BlockSpec:

  grid = (batch, q_heads, num_q_blocks, num_k_blocks)

The k-block axis is innermost ("revisiting" pattern): running max / sum /
accumulator live in VMEM scratch and the output block is finalised on the
last k iteration.  Handles causal masking, sliding windows and GQA (the
kv-head index map is ``h // group``), with padding masked via position iota.

Block sizes default to (128, 128) — MXU-aligned for the TPU target.
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

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int, seq_len: int,
                  block_q: int, block_k: int, num_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                   # [bq, d]
    k = k_ref[0, 0].astype(jnp.float32)                   # [bk, d]
    v = v_ref[0, 0].astype(jnp.float32)                   # [bk, d]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [bq, bk]

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos < seq_len                                # padding
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                   # [bq]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_cur[:, None])
    # rows with no valid key yet: keep exp(NEG_INF - NEG_INF) from blowing up
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_cur)
    l_cur = alpha * l_ref[...] + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_cur
    l_ref[...] = l_cur

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_bhld(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: int = 0,
                         scale: Optional[float] = None,
                         block_q: int = DEFAULT_BLOCK_Q,
                         block_k: int = DEFAULT_BLOCK_K,
                         interpret: Optional[bool] = None) -> jax.Array:
    """q [B, Hq, L, D], k/v [B, Hkv, L, D] → [B, Hq, L, D].

    ``interpret`` defaults to the platform (``kernels.interpret_mode``).
    """
    b, hq, sl, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    l_pad = -(-sl // max(block_q, block_k)) * max(block_q, block_k)
    if l_pad != sl:
        pad = ((0, 0), (0, 0), (0, l_pad - sl), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    nq = l_pad // block_q
    nk = l_pad // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window, seq_len=sl,
        block_q=block_q, block_k=block_k, num_k_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, h, qi, ki: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, h, qi, ki: (bi, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, h, qi, ki: (bi, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, h, qi, ki: (bi, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, l_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
            pltpu.VMEM((block_q,), jnp.float32),     # running max m
            pltpu.VMEM((block_q,), jnp.float32),     # running sum l
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
    return out[:, :, :sl, :]
