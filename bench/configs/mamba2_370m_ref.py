"""Plain float32 reference of mamba2_370m under the PerFedS² step.

Written from the Mamba-2 paper (arXiv:2405.21060: the block of Fig. 6 and
the minimal SSD algorithm of Listing 1) and the PerFedS² paper (Eq. 7, the
Per-FedAvg meta-gradient with a forward-over-reverse Hessian-vector
product, and the server's β-SGD step on the clipped gradient).  It imports
nothing of the program.  Every matmul and einsum runs at
``Precision.HIGHEST``; activations stay in float32.  After each step the
parameters are stored in the dtype the configuration states for each leaf
(bfloat16, and float32 for ``A_log``, ``dt_bias`` and ``D``), as the
program's state stores them.

``precision="default"`` runs every product at the TPU's default (one
bfloat16 pass on float32 operands, forward and backward), with all else
as above: it isolates the precision of the products, and is read only to
find the cause of a gap (``bench/calibrate.py --diag``).

The inner-adapted parameters w − α∇f(w; D_in) are parameters too, and are
stored in the configuration's dtypes like those after a step.  Two
readings look for the cause of a gap (``--diag``): ``adapt_f32=True``
keeps the adapted parameters in float32, and ``emulate=True`` follows the
program's own precision: the residual stream and every product's inputs
rounded to bfloat16, and the products at the TPU's default precision.

``lower=True`` gives the control: the same mathematics one precision
lower than the configuration states — every stored parameter in float8
e4m3 where the state is bfloat16 and in bfloat16 where it is float32, the
inputs of every dense product rounded to float8 e4m3 (saturating) and
those of the SSD einsums, which the configuration runs in float32, to
bfloat16.  The backward pass sees the roundings straight through.

Batches are consumed in blocks of rows and each layer is rematerialised,
so the reference of a full-size step fits beside nothing else on one chip.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PRECISION = {"highest": HIGHEST, "default": jax.lax.Precision.DEFAULT}
F32_LEAVES = ("A_log", "dt_bias", "D_skip")
LOWER = {"bfloat16": jnp.float8_e4m3fn, "float32": jnp.bfloat16}

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

FP8_MAX = 448.0      # largest finite float8 e4m3


@jax.custom_jvp
def _round8(x):
    """Round to float8 e4m3, saturating (e4m3 has no infinity).  Tangents
    and cotangents pass through unrounded (straight-through), as float8
    training keeps its gradients wider."""
    return jnp.clip(x, -FP8_MAX, FP8_MAX).astype(
        jnp.float8_e4m3fn).astype(jnp.float32)


@_round8.defjvp
def _round8_jvp(primals, tangents):
    return _round8(primals[0]), tangents[0]


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _segsum(x):
    """out[..., i, j] = Σ_{j < m ≤ i} x[..., m]; −inf above the diagonal."""
    t = x.shape[-1]
    xe = jnp.broadcast_to(x[..., :, None], x.shape + (t,))
    xe = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), xe, 0.0)
    cs = jnp.cumsum(xe, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool), 0), cs, -jnp.inf)


def _round16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


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
    q_ssd = _round16 if q is _round8 else q
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


# ---------------------------------------------------------------------------
# Eq. 7 and the server step, over blocks of rows
# ---------------------------------------------------------------------------

def _blocks(batch: Tree, rows: int) -> Tree:
    """[B, T] leaves → [B/rows, rows, T]."""
    return jax.tree.map(lambda v: v.reshape((-1, rows) + v.shape[1:]), batch)


def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


class Reference:
    """Jitted pieces of the reference step for one configuration."""

    def __init__(self, cfg: dict, *, rows: int, lower: bool = False,
                 precision: str = "highest", adapt_f32: bool = False,
                 emulate: bool = False):
        self.cfg, self.rows, self.lower = cfg, rows, lower
        self.adapt_f32 = adapt_f32
        q = _round8 if lower else (_round16 if emulate else None)
        lossf = functools.partial(
            loss, cfg=cfg, q=q,
            prec=PRECISION["default" if emulate else precision],
            act=_round16 if emulate else None)

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
def _reference(cfg_json: str, rows: int, lower: bool, **kw) -> Reference:
    """One set of jitted pieces per (configuration, block, precision)."""
    return Reference(json.loads(cfg_json), rows=rows, lower=lower, **kw)


def leaf_norms(tree: Tree) -> Dict[str, float]:
    return {p: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for p, v in flat(tree).items()}


def diff_norms(a: Tree, b: Tree) -> Dict[str, float]:
    fa, fb = flat(a), flat(b)
    return {p: float(jnp.sqrt(jnp.sum(jnp.square(
        fa[p].astype(jnp.float32) - fb[p].astype(jnp.float32)))))
        for p in fa}


def train_readings(cfg: dict, params0: Tree, steps: List[Tree], *,
                   rows: int, lower: bool = False, rows_used: int = 0,
                   **kw) -> Dict[str, Any]:
    """Follow the program's first ``len(steps)`` steps from ``params0``.

    ``steps[i]`` is step i's Eq.-7 triplet.  ``rows_used`` > 0 keeps only
    that many rows of each batch (the half-batch fault).  Returns each
    step's meta-objective and pre-clip gradient norm, the per-leaf norm of
    the first clipped gradient, and per-leaf norms of the stored change
    after one step and after all of them.  ``kw`` goes to ``Reference``
    (``precision``, ``adapt_f32``, ``emulate``).
    """
    tr = cfg["train"]
    ref = _reference(json.dumps(cfg, sort_keys=True), rows, lower, **kw)
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
