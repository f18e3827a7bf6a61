"""Per-FedAvg meta-gradient — Eq. (3)–(7) of the paper.

The PFL objective per client is ``F_i(w) = f_i(w − α ∇f_i(w))`` (Eq. 4) and
its gradient (Eq. 5):

    ∇F_i(w) = (I − α ∇²f_i(w)) ∇f_i(w − α ∇f_i(w))

The stochastic version (Eq. 7) uses three independent batches:
``D_in`` for the inner adaptation gradient, ``D_o`` for the outer gradient at
the adapted point, and ``D_h`` for the Hessian estimate.  We never materialise
the Hessian: ``(I − α∇²f)v = v − α·HVP(w, v)`` with the HVP computed by
forward-over-reverse ``jax.jvp`` through ``jax.grad`` — exact and O(params).

``perfed_value_and_grad`` returns the meta-objective value
``F̃_i(w) = f_i(w − α∇f_i(w; D_in); D_o)`` with the gradient: the value is the
outer gradient's own forward pass at the adapted point, under the same random
draws, so reporting the loss costs no extra pass.  ``perfed_grad`` is its
gradient alone.

``first_order=True`` gives the FO-MAML variant (drops the Hessian term).
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax

from repro.utils import tree_axpy

LossFn = Callable[..., Any]   # loss_fn(params, batch, rng) -> (scalar, aux)


def _scalar(loss_fn: LossFn, batch, rng):
    def scalar_loss(p):
        out = loss_fn(p, batch, rng)
        return out[0] if isinstance(out, tuple) else out
    return scalar_loss


def _grad(loss_fn: LossFn, params, batch, rng):
    return jax.grad(_scalar(loss_fn, batch, rng))(params)


def adapt(loss_fn: LossFn, params, batch, alpha: float, rng=None):
    """One inner SGD step: w' = w − α ∇f(w; D_in)  (the personalization step)."""
    with jax.named_scope("perfed.adapt"):
        g = _grad(loss_fn, params, batch, rng)
        return tree_axpy(-alpha, g, params)


def hvp(loss_fn: LossFn, params, batch, vector, rng=None):
    """Hessian-vector product ∇²f(w; D_h) · v via forward-over-reverse."""
    def grad_fn(p):
        return _grad(loss_fn, p, batch, rng)
    return jax.jvp(grad_fn, (params,), (vector,))[1]


def perfed_value_and_grad(loss_fn: LossFn, params, batches: Dict[str, Any],
                          alpha: float, *, first_order: bool = False,
                          rng=None):
    """(F̃_i(w), ∇̃F_i(w)): the meta-objective and its Eq.-(7) gradient.

    ``batches`` carries the three independent samples: ``{"inner": D_in,
    "outer": D_o, "hessian": D_h}``.  The value is f(w − α∇f(w; D_in); D_o)
    from the forward pass the outer gradient runs anyway, under that
    gradient's draw of ``rng``; ``perfed_loss`` splits ``rng`` differently, so
    the two agree wherever the loss ignores ``rng`` (every model in
    ``repro.models``).  The gradient is a pytree like ``params``.
    """
    r1 = r2 = r3 = None
    if rng is not None:
        r1, r2, r3 = jax.random.split(rng, 3)
    w_adapted = adapt(loss_fn, params, batches["inner"], alpha, r1)
    with jax.named_scope("perfed.outer"):
        value, g_outer = jax.value_and_grad(
            _scalar(loss_fn, batches["outer"], r2))(w_adapted)
    if first_order:
        return value, g_outer
    with jax.named_scope("perfed.hvp"):
        h = hvp(loss_fn, params, batches["hessian"], g_outer, r3)
        return value, tree_axpy(-alpha, h, g_outer)


def perfed_grad(loss_fn: LossFn, params, batches: Dict[str, Any], alpha: float,
                *, first_order: bool = False, rng=None):
    """Stochastic meta-gradient ∇̃F_i(w) of Eq. (7): the gradient of
    ``perfed_value_and_grad`` (XLA drops the unused value)."""
    return perfed_value_and_grad(loss_fn, params, batches, alpha,
                                 first_order=first_order, rng=rng)[1]


def perfed_loss(loss_fn: LossFn, params, batches: Dict[str, Any], alpha: float,
                rng=None):
    """F_i(w) = f_i(w − α∇f_i(w; D_in); D_o) — the meta-objective value
    alone.  A step that also needs the gradient takes both from
    ``perfed_value_and_grad``, which runs no second forward pass."""
    with jax.named_scope("perfed.loss"):
        r1, r2 = (jax.random.split(rng) if rng is not None else (None, None))
        w_adapted = adapt(loss_fn, params, batches["inner"], alpha, r1)
        out = loss_fn(w_adapted, batches["outer"], r2)
        return out[0] if isinstance(out, tuple) else out


def perfed_grad_exact(loss_fn: LossFn, params, batch, alpha: float, rng=None):
    """Autodiff oracle: d/dw f(w − α∇f(w)) on a single batch.

    Used by tests to validate `perfed_grad` — with identical batches for
    inner/outer/hessian the two must agree to numerical precision.
    """
    def meta_obj(p):
        w_ad = adapt(loss_fn, p, batch, alpha, rng)
        out = loss_fn(w_ad, batch, rng)
        return out[0] if isinstance(out, tuple) else out
    return jax.grad(meta_obj)(params)
