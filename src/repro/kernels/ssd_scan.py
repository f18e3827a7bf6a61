"""Mamba-2 SSD chunk kernel (TPU Pallas target; interpreted off the TPU).

The SSD algorithm splits the sequence into chunks; the quadratic chunk-local
work (decay matrix + scores + per-chunk state summaries) is the compute hot
spot and lives in this kernel.  The O(num_chunks) inter-chunk recurrence and
the rank-1 state broadcast stay in jnp (see ``ops.ssd_chunked``).

Per (batch, chunk) program, VMEM blocks:
  x [Q,H,P], dt [Q,H], b [Q,N], c [Q,N], a [H]  →
  y_intra [Q,H,P], state [H,P,N], chunk_decay [H], in_decay [H,Q]
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref,
                y_ref, st_ref, dec_ref, indec_ref):
    x = x_ref[0, 0].astype(jnp.float32)         # [Q,H,P]
    dt = dt_ref[0, 0].astype(jnp.float32)       # [Q,H]
    b = b_ref[0, 0].astype(jnp.float32)         # [Q,N]
    c = c_ref[0, 0].astype(jnp.float32)         # [Q,N]
    a = a_ref[...].astype(jnp.float32)          # [H]

    q = x.shape[0]
    da = (dt * a[None, :]).T                    # [H,Q] (≤ 0)
    cum = jnp.cumsum(da, axis=1)                # [H,Q]

    # decay matrix L[h,i,j] = exp(cum_i − cum_j) for j ≤ i else 0
    diff = cum[:, :, None] - cum[:, None, :]    # [H,Q,Q]
    tri = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lmat = jnp.where(tri[None], jnp.exp(diff), 0.0)

    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))   # [Q,Q]
    xdt = x * dt[..., None]                                        # [Q,H,P]
    y = jnp.einsum("ij,hij,jhp->ihp", scores, lmat, xdt)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    decay_end = jnp.exp(cum[:, -1:] - cum)                         # [H,Q]
    st = jnp.einsum("jn,hj,jhp->hpn", b, decay_end, xdt)
    st_ref[0, 0] = st.astype(st_ref.dtype)

    dec_ref[0, 0] = jnp.exp(cum[:, -1]).astype(dec_ref.dtype)
    indec_ref[0, 0] = jnp.exp(cum).astype(indec_ref.dtype)


def ssd_chunk_pallas(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                     c: jax.Array, *, interpret: Optional[bool] = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Chunk-local SSD terms.

    x [B,NC,Q,H,P], dt [B,NC,Q,H], a [H], b/c [B,NC,Q,N] →
      (y_intra [B,NC,Q,H,P], states [B,NC,H,P,N],
       chunk_decay [B,NC,H], in_decay [B,NC,H,Q])
    """
    bs, nc, qlen, h, p = x.shape
    n = b.shape[-1]
    grid = (bs, nc)

    return pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, qlen, h, p), lambda bi, ci: (bi, ci, 0, 0, 0)),
            pl.BlockSpec((1, 1, qlen, h), lambda bi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, qlen, n), lambda bi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, qlen, n), lambda bi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((h,), lambda bi, ci: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, qlen, h, p), lambda bi, ci: (bi, ci, 0, 0, 0)),
            pl.BlockSpec((1, 1, h, p, n), lambda bi, ci: (bi, ci, 0, 0, 0)),
            pl.BlockSpec((1, 1, h), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, 1, h, qlen), lambda bi, ci: (bi, ci, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bs, nc, qlen, h, p), jnp.float32),
            jax.ShapeDtypeStruct((bs, nc, h, p, n), jnp.float32),
            jax.ShapeDtypeStruct((bs, nc, h), jnp.float32),
            jax.ShapeDtypeStruct((bs, nc, h, qlen), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(x, dt, b, c, a)


# squeeze helper for the [0]-indexed block refs above: BlockSpec blocks carry
# the leading singleton grid dims, so refs are indexed with [0] / [0, 0].
