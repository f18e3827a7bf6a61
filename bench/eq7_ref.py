"""The plain reference of the PerFedS² step, for a model of any family.

Eq. 7 of the PerFedS² paper (the Per-FedAvg meta-gradient with a
forward-over-reverse Hessian-vector product) and the server's β-SGD step on
the clipped gradient, over the ``loss`` of a configuration's reference
module (``configs/<config>_ref.py``; its interface is in ``harness.py``).
It imports nothing of the program.  Activations stay in float32 and every
product runs at ``Precision.HIGHEST``.  After each step the parameters are
stored in the dtype the program's state keeps for each leaf, as the
program stores them.

The inner-adapted parameters w − α∇f(w; D_in) are parameters too, and are
stored in those dtypes like those after a step.  Two readings look for the
cause of a gap (``bench/calibrate.py --diag``): ``adapt_f32=True`` keeps
the adapted parameters in float32, and ``emulate=True`` follows the
program's own precision: the residual stream and every product's inputs
rounded to bfloat16, and the products at the TPU's default precision (one
bfloat16 pass).

``lower=True`` gives the control: the same mathematics one precision
lower than the configuration states — every stored parameter in float8
e4m3 where the state is bfloat16 and in bfloat16 where it is float32, and
the inputs of every product rounded to float8 e4m3 (saturating; the model
may round those of products it runs in float32 to bfloat16 instead).  The
backward pass sees the roundings straight through.

Batches are consumed in blocks of rows (the model rematerialises each
layer), so the reference of a full-size step fits beside nothing else on
one chip.
"""
from __future__ import annotations

import functools
import json
from types import ModuleType
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LOWER = {"bfloat16": jnp.float8_e4m3fn, "float32": jnp.bfloat16}
FP8_MAX = 448.0      # largest finite float8 e4m3

Tree = Dict[str, Any]


@jax.custom_jvp
def round8(x):
    """Round to float8 e4m3, saturating (e4m3 has no infinity).  Tangents
    and cotangents pass through unrounded (straight-through), as float8
    training keeps its gradients wider."""
    return jnp.clip(x, -FP8_MAX, FP8_MAX).astype(
        jnp.float8_e4m3fn).astype(jnp.float32)


@round8.defjvp
def _round8_jvp(primals, tangents):
    return round8(primals[0]), tangents[0]


def round16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def flat(tree: Tree, prefix: str = "") -> Dict[str, Any]:
    """Nested dict → {"a/b": leaf}."""
    out = {}
    for k, v in tree.items():
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flat(v, p + "/"))
        else:
            out[p] = v
    return out


def nest(paths: Dict[str, Any]) -> Tree:
    out: Tree = {}
    for p, v in paths.items():
        node = out
        *head, last = p.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def _blocks(batch: Tree, rows: int) -> Tree:
    """[B, T] leaves → [B/rows, rows, T]."""
    return jax.tree.map(lambda v: v.reshape((-1, rows) + v.shape[1:]), batch)


def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


class Reference:
    """Jitted pieces of the reference step for one configuration."""

    def __init__(self, model: ModuleType, cfg: dict, *, rows: int,
                 lower: bool = False, adapt_f32: bool = False,
                 emulate: bool = False):
        self.cfg, self.rows, self.lower = cfg, rows, lower
        self.adapt_f32 = adapt_f32
        q = round8 if lower else (round16 if emulate else None)
        lossf = functools.partial(
            model.loss, cfg=cfg, q=q,
            prec=jax.lax.Precision.DEFAULT if emulate else HIGHEST,
            act=round16 if emulate else None)

        def mean_grad(params, blocks):
            def body(acc, blk):
                val, g = jax.value_and_grad(lossf)(params, blk["tokens"],
                                                   blk["targets"])
                return (acc[0] + val, _add(acc[1], g)), None
            zero = (jnp.zeros((), jnp.float32),
                    jax.tree.map(jnp.zeros_like, params))
            (tot, g), _ = jax.lax.scan(body, zero, blocks)
            nb = blocks["tokens"].shape[0]
            return tot / nb, jax.tree.map(lambda v: v / nb, g)

        def mean_hvp(params, vec, blocks):
            def body(acc, blk):
                gfn = jax.grad(lambda p: lossf(p, blk["tokens"],
                                               blk["targets"]))
                return _add(acc, jax.jvp(gfn, (params,), (vec,))[1]), None
            h, _ = jax.lax.scan(body, jax.tree.map(jnp.zeros_like, params),
                                blocks)
            nb = blocks["tokens"].shape[0]
            return jax.tree.map(lambda v: v / nb, h)

        self.mean_grad = jax.jit(mean_grad)
        self.mean_hvp = jax.jit(mean_hvp)

    def stored(self, params32: Tree, dtypes: Dict[str, Any]) -> Tree:
        """Round float32 parameters to how the state stores them."""
        fl = flat(params32)
        out = {}
        for p, v in fl.items():
            dt = jnp.dtype(dtypes[p])
            if self.lower:
                dt = LOWER[dt.name]
                if dt == jnp.float8_e4m3fn:
                    v = jnp.clip(v, -FP8_MAX, FP8_MAX)
            out[p] = v.astype(dt).astype(jnp.float32)
        return nest(out)

    def eq7(self, params: Tree, batches: Tree, alpha: float, dtypes=None):
        """(meta-objective F(w), ∇̃F(w)) of Eq. 7 at float32 ``params``."""
        blk = {r: _blocks(batches[r], self.rows)
               for r in ("inner", "outer", "hessian")}
        _, g_in = self.mean_grad(params, blk["inner"])
        adapted = jax.tree.map(lambda w, g: w - alpha * g, params, g_in)
        if not self.adapt_f32:
            adapted = self.stored(adapted, dtypes)
        del g_in
        f_val, g_out = self.mean_grad(adapted, blk["outer"])
        del adapted
        h = self.mean_hvp(params, g_out, blk["hessian"])
        return f_val, jax.tree.map(lambda g, hv: g - alpha * hv, g_out, h)


@functools.lru_cache(maxsize=8)
def _reference(model: ModuleType, cfg_json: str, rows: int, lower: bool,
               **kw) -> Reference:
    """One set of jitted pieces per (model, configuration, block,
    precision)."""
    return Reference(model, json.loads(cfg_json), rows=rows, lower=lower,
                     **kw)


def leaf_norms(tree: Tree) -> Dict[str, float]:
    return {p: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for p, v in flat(tree).items()}


def diff_norms(a: Tree, b: Tree) -> Dict[str, float]:
    fa, fb = flat(a), flat(b)
    return {p: float(jnp.sqrt(jnp.sum(jnp.square(
        fa[p].astype(jnp.float32) - fb[p].astype(jnp.float32)))))
        for p in fa}


def train_readings(model: ModuleType, cfg: dict, params0: Tree,
                   steps: List[Tree], *, rows: int, lower: bool = False,
                   rows_used: int = 0, **kw) -> Dict[str, Any]:
    """Follow the program's first ``len(steps)`` steps from ``params0``,
    with the loss of the reference module ``model``.

    ``steps[i]`` is step i's Eq.-7 triplet.  ``rows_used`` > 0 keeps only
    that many rows of each batch (the half-batch fault).  Returns each
    step's meta-objective and pre-clip gradient norm, the per-leaf norm of
    the first clipped gradient, and per-leaf norms of the stored change
    after one step and after all of them.  ``kw`` goes to ``Reference``
    (``adapt_f32``, ``emulate``).
    """
    tr = cfg["train"]
    ref = _reference(model, json.dumps(cfg, sort_keys=True), rows, lower,
                     **kw)
    dtypes = {p: v.dtype for p, v in flat(params0).items()}
    w0 = ref.stored(jax.tree.map(lambda v: v.astype(jnp.float32), params0),
                    dtypes)
    w = w0
    out: Dict[str, Any] = {"loss": [], "grad_norm": []}
    for i, batches in enumerate(steps):
        if rows_used:
            batches = jax.tree.map(lambda v: v[:rows_used], batches)
        f_val, g = ref.eq7(w, batches, tr["alpha"], dtypes)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in
                          jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, tr["grad_clip"] / jnp.maximum(gn, 1e-12))
        g = jax.tree.map(lambda v: v * scale, g)
        if i == 0:
            out["grad_leaf"] = leaf_norms(g)
        w = ref.stored(jax.tree.map(lambda p, v: p - tr["beta"] * v, w, g),
                       dtypes)
        if i == 0:
            out["change_first"] = diff_norms(w, w0)
        out["loss"].append(float(f_val))
        out["grad_norm"].append(float(gn))
    out["change_last"] = diff_norms(w, w0)
    return out
