"""The training cell end to end on the CPU, and its comparison failing
with the timed path broken underneath."""
import functools

import jax
import pytest

from bench import compare, eq7_ref
from bench import train_common as tc
from bench.tests import cells

CELLS = cells.train_cells()


def run_with(cell_name, tmp_path, monkeypatch, breaker=None):
    cell = cells.tiny(cell_name)
    drv = cell.driver
    if breaker is not None:
        orig = drv.Program.__init__

        def init(self, ctx):
            orig(self, ctx)
            self.compiled = jax.jit(breaker(self.case.fn), donate_argnums=0)
        monkeypatch.setattr(drv.Program, "__init__", init)
    return drv.run(cells.ctx(cell, tmp_path))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tmp_path, monkeypatch):
    out = run_with(cell, tmp_path, monkeypatch)
    assert cells.correct(out), out.checks
    assert out.attempted >= 1 and out.compiles_in_window == 0
    assert out.e2e["train_tokens_per_s"] > 0


def unchanged(fn):
    def step(state, batches, rng):
        _, metrics = fn(state, batches, rng)
        return state, metrics
    return step


def half_batch(fn):
    def step(state, batches, rng):
        half = jax.tree.map(lambda v: v[: v.shape[0] // 2], batches)
        return fn(state, half, rng)
    return step


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("breaker", [unchanged, half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(cell, tmp_path, monkeypatch, breaker):
    out = run_with(cell, tmp_path, monkeypatch, breaker)
    assert not cells.correct(out), out.checks


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limits(cell):
    cell = cells.tiny(cell)
    ref, cfg, wl = cell.reference, cell.config, cell.workload
    params0 = jax.jit(functools.partial(ref.init, cfg=cfg))(
        jax.random.PRNGKey(5))
    lay = ref.layout(cfg)
    shapes = {r: {"tokens": jax.ShapeDtypeStruct(
        (wl["batch"], wl["seq_len"]), "int32")}
        for r in ("inner", "outer", "hessian")}
    steps = tc.draw_pool(shapes, cfg["vocab_size"], jax.random.PRNGKey(6),
                         wl["ref_steps"])
    assert set(eq7_ref.flat(params0)) == set(lay)
    sound = eq7_ref.train_readings(ref, cfg, params0, steps, rows=1)
    low = eq7_ref.train_readings(ref, cfg, params0, steps, rows=1,
                                 lower=True)
    gaps = compare.train_gaps(low, sound)
    assert any(gaps[k] > v for k, v in wl["limits"].items()), gaps
