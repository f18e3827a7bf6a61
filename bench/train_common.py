"""The flow of a training driver: build the program's compiled step
through ``launch.specs.build_case``, hand it weights and batches made from
the seed, drive its first steps (set-up, and the readings the reference
follows), time a closed loop of steps, then check against the reference.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import harness
from bench import trace as btrace


# the benchmark's own host spans, which name the idle gaps of a train cell
BENCH_SPANS = ("bench.dispatch", "bench.wait")


def keys_from_seed(seed: int, n: int = 2):
    """``n`` independent uint32 words of a seed of any size."""
    return [int(x) for x in np.random.SeedSequence(seed).generate_state(n)]


def flat_leaves(tree) -> Dict[str, Any]:
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)] = leaf
    return out


def check_layout(params_abs, ref_layout: dict) -> None:
    """The program's parameters are the leaves the reference defines."""
    got = {p: (tuple(v.shape), str(v.dtype))
           for p, v in flat_leaves(params_abs).items()}
    want = {p: (tuple(s), str(d)) for p, (s, d) in ref_layout.items()}
    if got != want:
        raise ValueError(f"parameter layout differs from the reference: "
                         f"program {got}, reference {want}")


def norms_fn():
    """Jitted per-leaf ‖a − b‖ (float32) of two parameter trees."""
    import jax
    import jax.numpy as jnp

    def f(a, b):
        return jax.tree.map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)
    return jax.jit(f)


@dataclasses.dataclass
class Timed:
    """What the window measured."""
    steps: int
    elapsed: float
    t_start: float
    compiles: int
    trace_dir: Optional[str] = None


def closed_loop(step: Callable[[int], Any], seconds: float, counter,
                *, trace_dir: Optional[str] = None) -> Timed:
    """Dispatch ``step(i)`` back to back for ``seconds``.

    The next step is dispatched before the host waits for the previous one
    (one step in flight), so the device never idles on the host; the
    window ends at the completion of the step during which ``seconds``
    ran out.  ``step`` returns something to wait on.
    """
    import jax

    if trace_dir:
        btrace.start(trace_dir)
    c0 = counter.n
    with btrace.span(btrace.WINDOW_SPAN):
        t_start = time.perf_counter()
        prev, i = None, 0
        while True:
            with btrace.span("bench.dispatch"):
                cur = step(i)
            i += 1
            if prev is not None:
                with btrace.span("bench.wait"):
                    jax.block_until_ready(prev)
            prev = cur
            if time.perf_counter() - t_start >= seconds:
                break
        with btrace.span("bench.wait"):
            jax.block_until_ready(prev)
        t_end = time.perf_counter()
    if trace_dir:
        jax.profiler.stop_trace()
    return Timed(i, t_end - t_start, t_start, counter.n - c0, trace_dir)


def draw_pool(shapes, vocab: int, key, n: int, shardings=None):
    """``n`` token batches shaped like ``shapes`` (a pytree of
    ShapeDtypeStructs), drawn on the device in one call.  Targets are the
    tokens shifted by one, as language-model training reads them."""
    import jax
    import jax.numpy as jnp

    def one(k, role_shapes):
        tok_shape = role_shapes["tokens"].shape
        seq = jax.random.randint(k, tok_shape[:-1] + (tok_shape[-1] + 1,),
                                 0, vocab, jnp.int32)
        return {"tokens": seq[..., :-1], "targets": seq[..., 1:]}

    def draw(k):
        out = []
        for kk in jax.random.split(k, n):
            ks = dict(zip(("inner", "outer", "hessian"),
                          jax.random.split(kk, 3)))
            out.append({r: one(ks[r], shapes[r]) for r in ks})
        return tuple(out)

    sh = None if shardings is None else tuple([shardings] * n)
    return list(jax.jit(draw, out_shardings=sh)(key))


def memory_gib(compiled) -> Optional[float]:
    """Planned peak per device of a compiled executable: arguments +
    outputs + temporaries − aliased bytes (``memory_analysis``)."""
    ma = compiled.memory_analysis()
    if ma is None:
        return None
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    return total / 2**30


def compare_checks(ctx: harness.Ctx, prog: dict, ref: dict
                   ) -> List[harness.Check]:
    from bench.compare import train_gaps

    limits = ctx.cell.workload["limits"]
    gaps = train_gaps(prog, ref)
    print(f"bench: gaps {json.dumps(gaps)}", file=sys.stderr, flush=True)
    return [harness.Check(k, float(v), float(limits[k]))
            for k, v in gaps.items() if k in limits]


def reference_readings(ctx: harness.Ctx, params0, steps: List[Any],
                       **kw) -> dict:
    """Run the plain reference of the cell's model over the followed
    steps."""
    from bench import eq7_ref

    return eq7_ref.train_readings(
        ctx.cell.reference, ctx.cell.config, params0, steps,
        rows=ctx.cell.workload["ref_block_rows"], **kw)


def init_params(ref, cfg: dict, key, shardings=None):
    import jax

    return jax.jit(functools.partial(ref.init, cfg=cfg),
                   out_shardings=shardings)(key)


def eq7_step(family, cfg: dict, batch: int, seq_len: int) -> float:
    """Model FLOPs of one cohort's Eq.-7 meta-gradient on ``batch`` rows of
    ``seq_len`` tokens per role (inner, outer, Hessian), from one forward
    pass per token (F, ``family.forward_per_token``):

    * a gradient is a forward (F) and a backward pass (2F: each product
      ``y = x·W`` costs ``dy·Wᵀ`` and ``xᵀ·dy``) — 3F;
    * the inner gradient on D_in and the outer gradient at the adapted
      point on D_o are 3F each;
    * the Hessian-vector product on D_h is forward-over-reverse: the primal
      gradient (3F) and its tangent, where every bilinear product ``a·b``
      gets ``da·b + a·db`` (6F) — 9F.

    So one step is 15·T·F on T = ``batch·seq_len`` tokens per role.  Not
    counted: recomputation under remat, and cohorts whose fresh gradient
    the step discards.
    """
    return 15.0 * batch * seq_len * family.forward_per_token(cfg, seq_len)


def train_artifacts(ctx: harness.Ctx, timed: Timed, compiled) -> dict:
    """What the per-layer readers of a training cell read."""
    from bench import hlo

    cfg, wl = ctx.cell.config, ctx.cell.workload
    chips = int(wl["chips"])
    tr = btrace.load(timed.trace_dir, chips)
    devs = tr.devices[:chips]
    return {
        "kind": "train",
        "trace": tr,
        "steps": timed.steps,
        "busy_s": sum(tr.busy_s(d) for d in devs) / len(devs),
        "window_s": tr.window_s(),
        "breakdown": tr.breakdown(devs[0], labels=BENCH_SPANS),
        "chips": chips,
        "peak_flops": ctx.peaks["bf16_flops"],
        "model_flops_step": eq7_step(ctx.cell.family, cfg, wl["batch"],
                                     wl["seq_len"]),
        "hlo_dot_flops_step": hlo.analyze_hlo(compiled.as_text())[
            "dot_flops_tc"],
        "hbm_gib": memory_gib(compiled),
    }
