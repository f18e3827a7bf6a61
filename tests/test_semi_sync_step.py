"""SPMD semi-synchronous step (core/semi_sync.py) semantics on one device."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ExperimentConfig, FLConfig, ModelConfig, TrainConfig
from repro.configs import get_config
from repro.core import perfed, semi_sync
from repro.models import build_model
from repro.optim import clip_by_global_norm, make_optimizer
from repro.utils import tree_norm, tree_sub


@pytest.fixture(scope="module")
def setup():
    cfg = ExperimentConfig(
        model=ModelConfig(name="mnist_dnn", family="small", d_model=16,
                          vocab_size=10, dtype="float32"),
        fl=FLConfig(alpha=0.02, beta=0.1, staleness_bound=2),
        train=TrainConfig(grad_clip=0.0))
    model = build_model(cfg.model)
    opt = make_optimizer("sgd")
    return cfg, model, opt


def _cohort_batches(rng, n_cohorts, b=8):
    def one(r):
        rx, ry = jax.random.split(r)
        return {"x": jax.random.normal(rx, (n_cohorts, b, 28, 28)),
                "y": jax.random.randint(ry, (n_cohorts, b), 0, 10)}
    r1, r2, r3 = jax.random.split(rng, 3)
    return {"inner": one(r1), "outer": one(r2), "hessian": one(r3)}


def test_masked_aggregation_matches_manual(setup, rng):
    cfg, model, opt = setup
    n = 3
    step = semi_sync.make_semi_sync_step(model, cfg, opt, n)
    state = semi_sync.init_state(model, rng, opt, n)
    # hand-fill buffers with known values
    bufs = jax.tree.map(
        lambda b: jnp.stack([jnp.full(b.shape[1:], float(i + 1), b.dtype)
                             for i in range(n)]), state.buffers)
    state = state._replace(buffers=bufs)
    mask = jnp.array([1.0, 0.0, 1.0])
    batches = _cohort_batches(rng, n)
    new_state, metrics = jax.jit(step)(state, batches, mask, rng)
    # Eq. (8): w ← w − β/2 · (buf_0 + buf_2) = w − 0.1/2·(1+3)
    delta = jax.tree.map(lambda new, old: new - old, new_state.params,
                         state.params)
    for leaf in jax.tree.leaves(delta):
        np.testing.assert_allclose(np.asarray(leaf), -0.1 / 2 * 4.0, atol=1e-5)


def test_refresh_only_scheduled_cohorts(setup, rng):
    cfg, model, opt = setup
    n = 3
    step = semi_sync.make_semi_sync_step(model, cfg, opt, n)
    state = semi_sync.init_state(model, rng, opt, n)
    mask = jnp.array([1.0, 0.0, 1.0])
    batches = _cohort_batches(rng, n)
    new_state, _ = jax.jit(step)(state, batches, mask, rng)
    # cohort 1 keeps zeros; 0 and 2 refreshed to non-zero fresh grads
    b0 = jax.tree.leaves(new_state.buffers)[0]
    assert float(jnp.abs(b0[1]).max()) == 0.0
    assert float(jnp.abs(b0[0]).max()) > 0.0
    assert float(jnp.abs(b0[2]).max()) > 0.0
    np.testing.assert_array_equal(np.asarray(new_state.staleness), [0, 1, 0])


def test_stale_cohort_forced_refresh(setup, rng):
    cfg, model, opt = setup
    n = 2
    step = jax.jit(semi_sync.make_semi_sync_step(model, cfg, opt, n))
    state = semi_sync.init_state(model, rng, opt, n)
    batches = _cohort_batches(rng, n)
    mask = jnp.array([1.0, 0.0])
    # S = 2: after 3 rounds of never being scheduled, cohort 1 must refresh
    for _ in range(3):
        state, _ = step(state, batches, mask, rng)
    assert int(state.staleness[1]) == 3
    state, _ = step(state, batches, mask, rng)
    assert int(state.staleness[1]) == 0       # τ > S triggered the refresh


def test_single_cohort_is_synchronous_perfedavg(setup, rng):
    """n_cohorts=1, mask=[1] ≡ make_train_step(perfed) after one warm-up
    round (the first semi-sync round applies the zero-initialised buffer)."""
    cfg, model, opt = setup
    semi = jax.jit(semi_sync.make_semi_sync_step(model, cfg, opt, 1))
    plain = jax.jit(semi_sync.make_train_step(model, cfg, opt,
                                              perfed_step=True))
    s_state = semi_sync.init_state(model, rng, opt, 1)
    p_state = semi_sync.init_train_state(model, rng, opt)
    batches = _cohort_batches(rng, 1)
    flat_batches = jax.tree.map(lambda x: x[0], batches)
    mask = jnp.ones((1,))
    # round 1: buffer zero → params unchanged, buffer filled
    s_state, _ = semi(s_state, batches, mask, rng)
    assert float(tree_norm(tree_sub(s_state.params, p_state.params))) < 1e-7
    # round 2 applies exactly the gradient plain computes
    s_state, _ = semi(s_state, batches, mask, rng)
    p_state, _ = plain(p_state, flat_batches, rng)
    err = float(tree_norm(tree_sub(s_state.params, p_state.params)))
    assert err < 1e-5, err


def test_perfed_step_takes_its_loss_from_the_outer_pass(rng):
    """The reported meta-loss comes from the outer gradient's forward pass:
    the step equals one that runs ``perfed_grad`` and ``perfed_loss`` apart,
    bit for bit, and compiles to fewer FLOPs.  The layer scan keeps XLA from
    merging the two forwards itself, so a second forward would show."""
    cfg = ExperimentConfig(model=get_config("mamba2_370m").reduced(),
                           fl=FLConfig(alpha=0.01, beta=0.05),
                           train=TrainConfig(grad_clip=1.0))
    model = build_model(cfg.model)
    opt = make_optimizer("sgd")
    fl = cfg.fl

    def reference(state, batches, r):
        grads = perfed.perfed_grad(model.loss, state.params, batches,
                                   fl.alpha, rng=r)
        loss = perfed.perfed_loss(model.loss, state.params, batches,
                                  fl.alpha, rng=r)
        grads, gnorm = clip_by_global_norm(grads, cfg.train.grad_clip)
        params, opt_state = make_optimizer("sgd").update(
            grads, state.opt_state, state.params, fl.beta)
        return semi_sync.TrainState(params, opt_state, state.step + 1), {
            "loss": loss, "grad_norm": gnorm}

    def tokens(key):
        toks = jax.random.randint(key, (2, 65), 0, cfg.model.vocab_size)
        return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    step = semi_sync.make_train_step(model, cfg, opt, perfed_step=True)
    state = semi_sync.init_train_state(model, rng, opt)
    k_in, k_out, k_h = jax.random.split(jax.random.PRNGKey(3), 3)
    batches = {"inner": tokens(k_in), "outer": tokens(k_out),
               "hessian": tokens(k_h)}

    new = jax.jit(step).lower(state, batches, rng).compile()
    ref = jax.jit(reference).lower(state, batches, rng).compile()
    flops_new = new.cost_analysis()["flops"]
    flops_ref = ref.cost_analysis()["flops"]
    assert flops_new <= 0.96 * flops_ref, (flops_new, flops_ref)

    s_new, m_new = new(state, batches, rng)
    s_ref, m_ref = ref(state, batches, rng)
    assert float(m_new["loss"]) == float(m_ref["loss"])
    for a, b in zip(jax.tree.leaves(s_new.params),
                    jax.tree.leaves(s_ref.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
