"""Driver ``train_step``: the single-cohort PerFedS² step.

``semi_sync.make_train_step(perfed_step=True)`` through
``launch.specs.build_case``, compiled with its shardings and its state
donated.  Each step takes one Eq.-7 triplet (inner, outer, Hessian) of
``batch × seq_len`` tokens per role from a pool drawn on the device from the
seed; every token of every step counts (π = 1, the one cohort refreshes).

Set-up runs the first three steps (warm-up, and the readings the reference
follows); the window is a closed loop of steps.
"""
from __future__ import annotations

import sys
import time

from bench import harness
from bench import train_common as tc

WARM_STEPS = 3


class Program:
    """The compiled step of one cell, built once per process."""

    def __init__(self, ctx: harness.Ctx):
        import jax

        from repro import sharding
        from repro.config import FLConfig, ShapeConfig, TrainConfig
        from repro.launch.mesh import make_host_mesh
        from repro.launch.specs import arch_rules, build_case

        self.ctx = ctx
        cfg, wl = ctx.cell.config, ctx.cell.workload
        tr = cfg["train"]
        model_cfg = ctx.cell.family.program_config(cfg)
        mesh = make_host_mesh()
        shape = ShapeConfig("bench", seq_len=wl["seq_len"],
                            global_batch=wl["batch"], kind="train")
        fl = FLConfig(alpha=tr["alpha"], beta=tr["beta"],
                      first_order=tr["first_order"])
        train = TrainConfig(seq_len=wl["seq_len"],
                            global_batch_size=wl["batch"],
                            grad_clip=tr["grad_clip"])
        with sharding.use_mesh(mesh, arch_rules(model_cfg, mesh)):
            self.case = build_case(model_cfg, shape, mesh, fl=fl,
                                   train=train, perfed_step=True)
            tc.check_layout(self.case.args[0].params,
                            ctx.cell.reference.layout(cfg))
            self.compiled = jax.jit(
                self.case.fn, in_shardings=self.case.in_shardings,
                out_shardings=self.case.out_shardings,
                donate_argnums=0).lower(*self.case.args).compile()
        self.norms = tc.norms_fn()
        self.copy = jax.jit(lambda t: jax.tree.map(lambda v: v + 0, t))
        self.tokens_step = 3 * wl["batch"] * wl["seq_len"]

    def inputs(self, seed: int, *, placed: bool = True):
        """(weights, batch pool, step keys) drawn from ``seed``."""
        import jax

        cfg, wl, ref = (self.ctx.cell.config, self.ctx.cell.workload,
                        self.ctx.cell.reference)
        k_w, k_b = tc.keys_from_seed(seed)
        sh = self.case.in_shardings
        params = tc.init_params(ref, cfg, jax.random.PRNGKey(k_w),
                                sh[0].params if placed else None)
        pool = tc.draw_pool(self.case.args[1], cfg["vocab_size"],
                            jax.random.PRNGKey(k_b), wl["pool"],
                            sh[1] if placed else None)
        rngs = list(jax.random.split(jax.random.PRNGKey(k_b ^ 0x5EED),
                                     wl["pool"]))
        return params, pool, rngs

    def first_steps(self, params, pool, rngs):
        """Run the first steps; return (state, program readings)."""
        import jax
        import jax.numpy as jnp

        from repro.core.semi_sync import TrainState

        state = TrainState(params, (), jax.device_put(
            jnp.zeros((), jnp.int32), self.case.in_shardings[0].step))
        w0 = jax.block_until_ready(self.copy(state.params))
        losses, gnorms, changes = [], [], []
        for i in range(WARM_STEPS):
            state, m = self.compiled(state, pool[i], rngs[i])
            losses.append(m["loss"])
            gnorms.append(m["grad_norm"])
            changes.append(jax.block_until_ready(
                self.norms(state.params, w0)))
        del w0
        return state, {
            "loss": [float(v) for v in losses],
            "grad_norm": [float(v) for v in gnorms],
            "changes": [{p: float(v) for p, v in
                         tc.flat_leaves(c).items()} for c in changes]}


def calibrate(ctx: harness.Ctx, seeds, n_control: int, n_fault: int,
              n_diag: int = 0):
    """Per seed: the program's gaps to the reference, with each counted
    leaf's readings; on the first seeds also the control's and the
    half-batch fault's (``bench/calibrate.py``), and on the first
    ``n_diag`` the look for the cause of a gap: the same readings against a
    reference that keeps the inner-adapted parameters in float32, and, on
    the control's seeds, the program against a reference at its own
    precision (``emulate``).  A state left unchanged reads 1 on
    ``grad_gap`` and ``change_gap`` by construction and needs no run."""
    from bench.compare import counted, leaf_gaps, train_gaps

    wl = ctx.cell.workload
    n = wl["ref_steps"]
    program = Program(ctx)

    def reading(other, ref):
        leaves = counted(ref["grad_leaf"])
        first = leaf_gaps(other["change_first"], ref["change_first"], leaves)
        last = leaf_gaps(other["change_last"], ref["change_last"], leaves)
        return {"gaps": train_gaps(other, ref),
                "loss": other["loss"], "grad_norm": other["grad_norm"],
                "by_leaf": {p: {"first": [other["change_first"][p],
                                          ref["change_first"][p], first[p]],
                                "last": [other["change_last"][p],
                                         ref["change_last"][p], last[p]],
                                "ref_grad": ref["grad_leaf"][p]}
                            for p in leaves}}

    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        params, pool, rngs = program.inputs(seed)
        state, prog = program.first_steps(params, pool, rngs)
        del state, params, pool
        params0, steps, _ = program.inputs(seed, placed=False)
        steps = steps[:n]
        ref = tc.reference_readings(ctx, params0, steps)
        rec = {"seed": seed, "ref_loss": ref["loss"],
               "ref_grad_norm": ref["grad_norm"],
               "leaves": [len(counted(ref["grad_leaf"])),
                          len(ref["grad_leaf"])],
               "sound": reading(followed(prog, n), ref)}
        if i < n_control:
            ctl = tc.reference_readings(ctx, params0, steps, lower=True)
            rec["control"] = reading(ctl, ref)
        if i < n_fault:
            half = tc.reference_readings(ctx, params0, steps,
                                         rows_used=wl["batch"] // 2)
            rec["half_batch"] = reading(half, ref)
        if i < n_diag:
            # the same with the adapted parameters kept in float32, and the
            # reference at the program's own precision
            ref32 = tc.reference_readings(ctx, params0, steps, adapt_f32=True)
            rec["program_vs_adapt_f32"] = reading(followed(prog, n), ref32)
            rec["adapt_f32_vs_stored"] = reading(ref32, ref)
            if i < n_control:
                rec["control_vs_adapt_f32"] = reading(tc.reference_readings(
                    ctx, params0, steps, lower=True, adapt_f32=True), ref32)
            if i < n_fault:
                rec["half_batch_vs_adapt_f32"] = reading(
                    tc.reference_readings(ctx, params0, steps, adapt_f32=True,
                                          rows_used=wl["batch"] // 2), ref32)
            if i < n_control:
                emu = tc.reference_readings(ctx, params0, steps,
                                            emulate=True)
                rec["emulate_vs_ref"] = reading(emu, ref)
                rec["program_vs_emulate"] = reading(followed(prog, n), emu)
        rec["seconds"] = time.perf_counter() - t0
        yield rec


def followed(prog: dict, n: int) -> dict:
    """The program's readings over the first ``n`` steps."""
    return {"loss": prog["loss"][:n], "grad_norm": prog["grad_norm"][:n],
            "change_first": prog["changes"][0],
            "change_last": prog["changes"][n - 1]}


def run(ctx: harness.Ctx) -> harness.Outcome:
    import jax
    import jax.numpy as jnp

    counter = harness.CompileCounter()
    ctx.phase("import")
    wl = ctx.cell.workload
    program = Program(ctx)
    ctx.phase("compile")
    params, pool, rngs = program.inputs(ctx.seed)
    jax.block_until_ready((params, pool))
    ctx.phase("data")
    state, prog = program.first_steps(params, pool, rngs)
    del params
    ctx.phase("warmup")

    holder = {"state": state}
    del state
    compiled = program.compiled

    def step(i):
        j = (WARM_STEPS + i) % wl["pool"]
        holder["state"], m = compiled(holder["state"], pool[j], rngs[j])
        return m["loss"]

    trace_dir = str(ctx.outdir / "trace") if ctx.trace else None
    timed = tc.closed_loop(step, ctx.seconds, counter, trace_dir=trace_dir)
    mem_peak = harness.memory_peak_bytes(ctx.devices or jax.devices()[:1])
    finite = bool(jnp.isfinite(
        holder["state"].params["final_norm"]["scale"]).all())
    art = {}
    if ctx.trace:
        art = tc.train_artifacts(ctx, timed, compiled)
    del holder, pool

    # the reference follows the first steps, from the same seed
    n_ref = wl["ref_steps"]
    params0, steps, _ = program.inputs(ctx.seed, placed=False)
    t_ref = time.perf_counter()
    ref_out = tc.reference_readings(ctx, params0, steps[:n_ref])
    print(f"bench: reference {time.perf_counter() - t_ref:.1f} s over "
          f"{n_ref} steps", file=sys.stderr, flush=True)
    checks = tc.compare_checks(ctx, followed(prog, n_ref), ref_out)

    out = harness.Outcome(
        e2e={"train_tokens_per_s":
             timed.steps * program.tokens_step / timed.elapsed},
        t_window=timed.t_start, setup_split=dict(ctx.phases),
        compiles_in_window=timed.compiles, attempted=timed.steps,
        failed=0 if finite else timed.steps, checks=checks, art=art,
        memory_peak_bytes=mem_peak)
    if ctx.trace:
        out.busy_s, out.window_s = art["busy_s"], art["window_s"]
        out.breakdown = art["breakdown"]
    return out
