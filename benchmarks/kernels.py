"""Pallas kernels: correctness deltas vs the jnp oracles + oracle wall time.

NOTE on timing: off the TPU the kernels run in the Pallas interpreter
(Python emulation) — wall-clock of the kernel itself is meaningless.  We therefore
report the XLA-compiled ORACLE's time as ``us_per_call`` (the baseline a TPU
kernel must beat) and put the kernel-vs-oracle max error in ``derived``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timed


def run() -> None:
    from repro.kernels import ops, ref
    from repro.kernels.flash_attention import flash_attention_bhld
    from repro.kernels.fused_adam import fused_adam_flat
    from repro.kernels.stale_aggregate import stale_aggregate_flat

    rng = jax.random.PRNGKey(0)

    # flash attention
    b, hq, hkv, sl, d = 2, 8, 2, 256, 64
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, hq, sl, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, sl, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, sl, d), jnp.float32)
    oracle = jax.jit(lambda q, k, v: ref.attention_ref(q, k, v, causal=True))
    want, us = timed(lambda: jax.block_until_ready(oracle(q, k, v)))
    got = flash_attention_bhld(q, k, v, causal=True, block_q=128, block_k=128)
    err = float(jnp.max(jnp.abs(got - oracle(q, k, v))))
    emit("kernel/flash_attention", us, f"max_err={err:.2e};shape=b{b}h{hq}l{sl}d{d}")

    # ssd chunk scan
    from repro.models.ssm import ssd_chunked as ssd_jnp
    bs, L, H, P, N = 2, 512, 4, 16, 32
    ks = jax.random.split(jax.random.fold_in(rng, 1), 5)
    x = jax.random.normal(ks[0], (bs, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bs, L, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)))
    bm = jax.random.normal(ks[3], (bs, L, N))
    cm = jax.random.normal(ks[4], (bs, L, N))
    oracle2 = jax.jit(lambda *args: ssd_jnp(*args, 64))
    (y_ref, _), us = timed(
        lambda: jax.block_until_ready(oracle2(x, dt, a, bm, cm)))
    y_k, _ = ops.ssd_chunked(x, dt, a, bm, cm, 64)
    err = float(jnp.max(jnp.abs(y_k - y_ref)))
    emit("kernel/ssd_scan", us, f"max_err={err:.2e};shape=b{bs}l{L}h{H}")

    # fused adam
    n = 1 << 16
    ks = jax.random.split(jax.random.fold_in(rng, 2), 4)
    p = jax.random.normal(ks[0], (n,))
    m = jnp.zeros(n)
    vv = jnp.zeros(n)
    g = jax.random.normal(ks[1], (n,))
    oracle3 = jax.jit(lambda p, m, v, g: ref.adam_ref(
        p, m, v, g, lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, t=1))
    (rp, _, _), us = timed(lambda: jax.block_until_ready(oracle3(p, m, vv, g)))
    kp, _, _ = fused_adam_flat(p, m, vv, g, lr=1e-3, t=1)
    emit("kernel/fused_adam", us,
         f"max_err={float(jnp.max(jnp.abs(kp - rp))):.2e};n={n}")

    # stale aggregate
    c = 4
    buf = jax.random.normal(ks[2], (c, n))
    mask = jnp.array([1., 0., 1., 1.])
    oracle4 = jax.jit(lambda p, b, m: ref.stale_aggregate_ref(
        p, b, m, beta=0.07))
    want, us = timed(lambda: jax.block_until_ready(oracle4(p, buf, mask)))
    got = stale_aggregate_flat(p, buf, mask, beta=0.07)
    emit("kernel/stale_aggregate", us,
         f"max_err={float(jnp.max(jnp.abs(got - want))):.2e};c={c};n={n}")
