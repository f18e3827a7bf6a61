"""Jit'd public wrappers around the Pallas kernels.

Models call these through ``cfg.attn_impl == "pallas"`` etc.; tests compare
each against the pure-jnp oracles in ``ref.py``.  Every kernel picks interpret
mode from the platform (``kernels.interpret_mode``), so the same call runs
the Pallas interpreter on the CPU and the compiled kernel on a TPU.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhld
from repro.kernels.fused_adam import fused_adam_flat
from repro.kernels.ssd_scan import ssd_chunk_pallas
from repro.kernels.stale_aggregate import stale_aggregate_flat

# ``ref`` / ``stale_aggregate_flat`` / the tree aggregators below are
# deliberate ops.* passthroughs (historical public entry points).
__all__ = [
    "flash_attention",
    "fused_adam_tree",
    "masked_aggregate_tree",
    "ref",
    "ssd_chunked",
    "stale_aggregate_flat",
    "stale_aggregate_tree",
]

@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0) -> jax.Array:
    """Model-layout wrapper: q [B,L,H,D], k/v [B,L,Hkv,D] → [B,L,H,D]."""
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    out = flash_attention_bhld(qt, kt, vt, causal=causal, window=window)
    return jnp.moveaxis(out, 1, 2)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, chunk: int) -> Tuple[jax.Array, jax.Array]:
    """Pallas-backed drop-in for ``models.ssm.ssd_chunked``.

    x [B,L,H,P], dt [B,L,H], a [H], b/c [B,L,N] → (y [B,L,H,P], final_state).
    """
    bs, sl, h, p = x.shape
    n = b.shape[-1]
    assert sl % chunk == 0
    nc = sl // chunk
    xr = x.reshape(bs, nc, chunk, h, p)
    dtr = dt.reshape(bs, nc, chunk, h)
    br = b.reshape(bs, nc, chunk, n)
    cr = c.reshape(bs, nc, chunk, n)

    y_intra, states, chunk_decay, in_decay = ssd_chunk_pallas(
        xr.astype(jnp.float32), dtr.astype(jnp.float32),
        a.astype(jnp.float32), br.astype(jnp.float32), cr.astype(jnp.float32))

    # inter-chunk recurrence (linear in num_chunks — stays in jnp)
    def step(s_prev, inp):
        dec, st = inp
        return s_prev * dec[..., None, None] + st, s_prev

    s0 = jnp.zeros((bs, h, p, n), jnp.float32)
    s_final, s_prevs = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, 1, 0),
                   jnp.moveaxis(states, 1, 0)))
    s_prevs = jnp.moveaxis(s_prevs, 0, 1)                    # [B,NC,H,P,N]
    y_inter = jnp.einsum("bzin,bzhi,bzhpn->bzihp",
                         cr.astype(jnp.float32), in_decay, s_prevs)
    y = (y_intra + y_inter).reshape(bs, sl, h, p)
    return y.astype(x.dtype), s_final.astype(x.dtype)


def fused_adam_tree(params, m, v, grads, *, lr, t, b1=0.9, b2=0.95, eps=1e-8):
    """Pytree fused-Adam: applies the flat kernel leaf-wise."""
    def upd(p, mi, vi, g):
        shape = p.shape
        np_, nm, nv = fused_adam_flat(
            p.reshape(-1), mi.reshape(-1), vi.reshape(-1),
            g.reshape(-1).astype(jnp.float32), lr=lr, t=t, b1=b1, b2=b2,
            eps=eps)
        return np_.reshape(shape), nm.reshape(shape), nv.reshape(shape)

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_m = treedef.flatten_up_to(m)
    flat_v = treedef.flatten_up_to(v)
    flat_g = treedef.flatten_up_to(grads)
    out = [upd(*args) for args in zip(flat_p, flat_m, flat_v, flat_g)]
    new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
    return new_p, new_m, new_v


# Pytree Eq.-(8) update now lives in kernels/stale_aggregate.py as the
# unified aggregation API (single concat buffer + cached treedef) — this
# re-export keeps the historical ops.* entry point working.
from repro.kernels.stale_aggregate import (  # noqa: E402
    masked_aggregate_tree,
    stale_aggregate_tree,
)
