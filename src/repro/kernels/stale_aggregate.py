"""Masked stale-gradient aggregation — Eq. (8) — behind ONE API.

Flat Pallas kernel (TPU target; interpreted off the TPU):

    w ← w − (β/A) Σ_c π_c · buf_c

Fusing the masked reduction over the cohort axis with the parameter update
reads each buffer slot exactly once and writes w once — the unfused graph
materialises the Σ intermediate in HBM.  Cohort count is small and static,
so the reduction is an unrolled VMEM loop.

On top of the flat kernel sit the *tree* entry points that all protocol code
(``core/server.py``, ``core/semi_sync.py``, ``fl/engine.py``) now shares
instead of hand-rolling ``tree_map`` reductions:

* ``stale_aggregate_tree``   — fused Eq. (8) update of a parameter pytree
  from C payload pytrees (list or stacked) and a weight mask.
* ``masked_aggregate_tree``  — the masked *mean* alone (for callers that
  clip / feed a server optimizer before applying).

Both flatten through a cached ``utils.tree.TreeFlattener`` (one concat
buffer, treedef derived once per structure) and pick the backend:
``"pallas"`` runs the kernel (interpreted off-TPU), ``"jnp"`` a pure-JAX
matvec, ``"auto"`` uses Pallas only on a real TPU — interpret mode is a
correctness oracle, not a fast path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.utils.tree import TreeFlattener

BLOCK = 4096
# Eq. (8) is an f32 weighted sum, as the kernel computes it; a TPU dot at
# default precision would round the buffers to bf16 first
_F32 = jax.lax.Precision.HIGHEST


def _agg_kernel(scal_ref, mask_ref, p_ref, buf_ref, out_ref, *, n_cohorts: int):
    beta_over_a = scal_ref[0]
    acc = jnp.zeros(p_ref.shape, jnp.float32)
    for c in range(n_cohorts):                     # static unroll (C is small)
        acc = acc + mask_ref[c] * buf_ref[c].astype(jnp.float32)
    out_ref[...] = (p_ref[...].astype(jnp.float32)
                    - beta_over_a * acc).astype(out_ref.dtype)


def stale_aggregate_flat(params: jax.Array, buffers: jax.Array,
                         mask: jax.Array, *, beta: float,
                         block: int = BLOCK, interpret: Optional[bool] = None
                         ) -> jax.Array:
    """params [N], buffers [C, N], mask [C] → updated params [N]."""
    n = params.shape[0]
    c = buffers.shape[0]
    n_pad = -(-n // block) * block
    if n_pad != n:
        params = jnp.pad(params, (0, n_pad - n))
        buffers = jnp.pad(buffers, ((0, 0), (0, n_pad - n)))
    a = jnp.maximum(mask.sum(), 1.0)
    scal = jnp.stack([jnp.asarray(beta, jnp.float32) / a])
    kernel = functools.partial(_agg_kernel, n_cohorts=c)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // block,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # β/A
            pl.BlockSpec(memory_space=pltpu.SMEM),           # mask [C]
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((c, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), params.dtype),
        interpret=interpret_mode(interpret),
    )(scal, mask.astype(jnp.float32), params, buffers)
    return out[:n]


# ---------------------------------------------------------------------------
# Tree-level unified API
# ---------------------------------------------------------------------------

def _resolve_backend(backend: str) -> str:
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend not in ("pallas", "jnp"):
        raise ValueError(f"unknown aggregation backend {backend!r}")
    return backend


def _stack_payloads(payloads, flat: TreeFlattener) -> jax.Array:
    """List of payload pytrees OR stacked tree (leading C axis) → [C, N]."""
    if isinstance(payloads, (list, tuple)):
        return jnp.stack([flat.flatten(p) for p in payloads])
    return flat.flatten_stacked(payloads)


def stale_aggregate_update(p_flat: jax.Array, buf: jax.Array,
                           mask: jax.Array, *, beta,
                           backend: str = "auto") -> jax.Array:
    """Flat-buffer Eq. (8):  p − (β/A) Σ_c mask_c·buf_c,  A = max(Σ mask, 1).

    The one entry point every aggregation caller funnels through — the
    Pallas kernel on real TPUs, a pure-JAX matvec elsewhere.  Jit-traceable
    (the engine's fused round function calls it on tracers).
    """
    backend = _resolve_backend(backend)
    mask = mask.astype(jnp.float32)
    if backend == "pallas":
        return stale_aggregate_flat(p_flat, buf, mask, beta=beta)
    a = jnp.maximum(mask.sum(), 1.0)
    agg = jnp.dot(mask, buf, precision=_F32)
    return p_flat - (jnp.asarray(beta, jnp.float32) / a) * agg


def _stack_leafwise(payloads):
    """List of payload pytrees → one pytree with a leading cohort axis."""
    if isinstance(payloads, (list, tuple)):
        return jax.tree.map(
            lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *payloads)
    return payloads


def masked_aggregate_tree(payloads, mask: jax.Array):
    """Σ_c mask_c · payload_c / max(Σ mask, 1) as an f32 pytree.

    ``payloads`` is a list of pytrees or one pytree with a leading cohort
    axis.  Leaf-wise reduction (XLA fuses it; no concat buffer needed for a
    masked mean).
    """
    stacked = _stack_leafwise(payloads)
    mask = mask.astype(jnp.float32)
    a = jnp.maximum(mask.sum(), 1.0)
    return jax.tree.map(
        lambda bl: jnp.tensordot(mask, bl.astype(jnp.float32), axes=1,
                                 precision=_F32) / a,
        stacked)


def stale_aggregate_tree(params, payloads, mask: jax.Array, *, beta: float,
                         backend: str = "auto") -> object:
    """Fused Eq. (8) on pytrees:  w ← w − (β/A) Σ_c mask_c · payload_c,
    A = max(Σ mask, 1).  Returns a tree shaped/typed like ``params``.

    A staleness-discounted update (server ``staleness_discount`` < 1) is the
    same call with ``mask_c = λ^{τ_c} · A / Σ λ^{τ}`` — the weights fold
    into the mask, so sync/semi/async and SAFA-style variants all hit this
    one code path.

    The Pallas backend flattens through the cached ``TreeFlattener`` into
    the single concat buffer the kernel wants; the pure-JAX backend reduces
    leaf-wise (bench: ~1.5× faster than materialising the [C, N] concat on
    CPU — XLA fuses the per-leaf masked sums into the update).
    """
    backend = _resolve_backend(backend)
    mask = mask.astype(jnp.float32)
    if backend == "pallas":
        flat = TreeFlattener.for_tree(params)
        p = flat.flatten(params)
        buf = _stack_payloads(payloads, flat)
        out = stale_aggregate_update(p, buf, mask, beta=beta,
                                     backend=backend)
        return flat.unflatten(out)
    stacked = _stack_leafwise(payloads)
    a = jnp.maximum(mask.sum(), 1.0)
    scale = jnp.asarray(beta, jnp.float32) / a

    def upd(pl, bl):
        agg = jnp.tensordot(mask, bl.astype(jnp.float32), axes=1,
                            precision=_F32)
        return (pl.astype(jnp.float32) - scale * agg).astype(
            jnp.asarray(pl).dtype)

    return jax.tree.map(upd, params, stacked)
